"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060 §6): the sequence is
tiled into chunks of Q tokens; the grid is (batch, n_chunks) with the chunk
dim innermost-sequential, so the running inter-chunk SSM state (H,P,N) lives
in VMEM scratch and is carried across chunk iterations — the TPU analogue of
the GPU kernel's persistent-CTA state.  Per chunk, the three einsums
(intra-chunk CB^T "attention-like" block, state write, state read) are MXU
matmuls over (Q,P)x(Q,N)-shaped tiles.

Layout note: heads are folded into the grid's batch dim outside the kernel
(B*H program instances) so a single head's (Q,P)/(Q,N) tiles stay small
enough for VMEM at any head count.

Validated in interpret mode against kernels/ref.py::ssd_ref (sequential
recurrence — a fully independent oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _ssd_kernel(x_ref, dtc_ref, b_ref, c_ref, y_ref, state_out_ref, h_scr, *,
                chunk: int):
    cb = pl.program_id(1)
    n_chunks = pl.num_programs(1)

    @pl.when(cb == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q,P)  dt-scaled inputs
    dA = dtc_ref[0].astype(jnp.float32)       # (Q,)   log-decay increments
    Bm = b_ref[0].astype(jnp.float32)         # (Q,N)
    Cm = c_ref[0].astype(jnp.float32)         # (Q,N)

    cum = jnp.cumsum(dA)                      # (Q,)
    # ---- intra-chunk: y_ij = C_i . B_j * exp(cum_i - cum_j), j <= i
    CB = Cm @ Bm.T                            # (Q,Q) MXU
    diff = cum[:, None] - cum[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(tri, jnp.exp(diff), 0.0)
    y = (CB * L) @ x                          # (Q,P) MXU

    # ---- inter-chunk read: contribution of the carried state
    h_prev = h_scr[...]                       # (P,N)
    y += jnp.exp(cum)[:, None] * (Cm @ h_prev.T)   # (Q,N)@(N,P) MXU

    # ---- state update: h = decay(chunk) * h + sum_j exp(cum_Q - cum_j) x_j B_j
    decay_to_end = jnp.exp(cum[-1] - cum)     # (Q,)
    h_new = jnp.exp(cum[-1]) * h_prev + \
        (x * decay_to_end[:, None]).T @ Bm    # (P,Q)@(Q,N) MXU
    h_scr[...] = h_new

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(cb == n_chunks - 1)
    def _flush():
        state_out_ref[0] = h_new.astype(state_out_ref.dtype)


def ssd_scan_fwd(x, dt, A_log, Bm, Cm, *, chunk: int = 64, D=None,
                 interpret: bool | None = None):
    """x (B,S,H,P), dt (B,S,H) post-softplus, Bm/Cm (B,S,G,N).
    ``interpret=None`` follows ``repro.kernels.interpret_mode``.

    Returns (y (B,S,H,P) f32, final_state (B,H,P,N) f32).
    """
    if interpret is None:
        interpret = kernels.interpret_mode()
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    NC = Sp // chunk

    f32 = jnp.float32
    A = -jnp.exp(A_log.astype(f32))                       # (H,)
    dA = dt.astype(f32) * A[None, None, :]                # (B,Sp,H)
    xd = x.astype(f32) * dt.astype(f32)[..., None]        # dt-scaled inputs

    # fold heads into the grid batch dim: (B*H, Sp, ...)
    xh = xd.transpose(0, 2, 1, 3).reshape(Bsz * H, Sp, P)
    dAh = dA.transpose(0, 2, 1).reshape(Bsz * H, Sp)
    Bh = jnp.repeat(Bm.astype(f32), rep, axis=2).transpose(0, 2, 1, 3) \
        .reshape(Bsz * H, Sp, N)
    Ch = jnp.repeat(Cm.astype(f32), rep, axis=2).transpose(0, 2, 1, 3) \
        .reshape(Bsz * H, Sp, N)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, state = pl.pallas_call(
        kernel,
        grid=(Bsz * H, NC),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk), lambda b, c: (b, c)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, P, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz * H, Sp, P), f32),
            jax.ShapeDtypeStruct((Bsz * H, P, N), f32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xh, dAh, Bh, Ch)

    y = y.reshape(Bsz, H, Sp, P).transpose(0, 2, 1, 3)[:, :S]
    state = state.reshape(Bsz, H, P, N)
    if D is not None:
        y = y + x[:, :S].astype(f32) * D.astype(f32)[None, None, :, None]
    return y, state
