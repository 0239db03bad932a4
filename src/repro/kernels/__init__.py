"""Pallas TPU kernels: one module per kernel, jitted wrappers in ops.py and
pure-jnp oracles in ref.py.

One rule decides how a kernel runs, read from the backend and from nothing
else: it compiles through Mosaic on a TPU and runs in interpret mode on the
CPU, where the tests and parity oracles run.
"""
import jax


def interpret_mode() -> bool:
    """True on the CPU backend (interpret the kernel), False on a TPU
    (compile it)."""
    return jax.default_backend() == "cpu"
