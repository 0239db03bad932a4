"""Model assemblies: dense/MoE/VLM decoder LM, Mamba2 LM, Zamba2 hybrid,
Seamless enc-dec.  Homogeneous layer stacks are `lax.scan`ned over stacked
params (optionally rematerialized) to keep HLO size ~O(1) in depth — required
for the 512-device dry-run compiles.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.params import ParamSpec, stack_specs

PREFIX_EMBED_DIM = 1024  # stubbed vision/audio frontend output width


# =============================================================== decoder layer
def decoder_layer_specs(cfg, moe_layer: bool) -> dict:
    specs = {
        "ln1": L.rmsnorm_specs(cfg.d_model),
        "attn": attn.attention_specs(cfg),
        "ln2": L.rmsnorm_specs(cfg.d_model),
    }
    if moe_layer:
        specs["moe"] = MOE.moe_specs(cfg)
    else:
        specs["mlp"] = L.mlp_specs(cfg.d_model, cfg.d_ff)
    return specs


def decoder_layer_apply(p, cfg, x, positions, cache=None, window: int = 0,
                        use_flash: bool = False, moe_dense_ref: bool = False,
                        kv_valid=None, paged_kernel: bool = False):
    h, new_cache = attn.attention_apply(
        p["attn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps), positions,
        cache=cache, window=window, use_flash=use_flash, kv_valid=kv_valid,
        paged_kernel=paged_kernel)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        fn = MOE.moe_apply_dense if moe_dense_ref else MOE.moe_apply
        h, aux = fn(p["moe"], cfg, L.rmsnorm(p["ln2"], x, cfg.norm_eps))
    else:
        h = L.mlp(p["mlp"], L.rmsnorm(p["ln2"], x, cfg.norm_eps))
    return x + h, new_cache, aux


# =============================================================== decoder stack
def decoder_stack_specs(cfg) -> dict:
    n_moe = cfg.n_layers - cfg.first_k_dense if cfg.n_experts else 0
    specs: dict = {}
    if cfg.n_experts:
        if cfg.first_k_dense:
            dense_cfg_layer = decoder_layer_specs(cfg, moe_layer=False)
            specs["dense_layers"] = [dense_cfg_layer for _ in range(cfg.first_k_dense)]
        specs["layers"] = stack_specs(decoder_layer_specs(cfg, moe_layer=True),
                                      n_moe, "layers")
    else:
        specs["layers"] = stack_specs(decoder_layer_specs(cfg, moe_layer=False),
                                      cfg.n_layers, "layers")
    return specs


def _remat(cfg, fn):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _scan_layers(stacked_params, cfg, x, layer_fn, caches=None):
    """Scan ``layer_fn(params_l, x, cache_l) -> (x, new_cache_l, aux)`` over L."""
    def body(carry, xs):
        x, aux = carry
        p_l, cache_l = xs
        x, new_cache, a = layer_fn(p_l, x, cache_l)
        return (x, aux + a), new_cache

    if cfg.remat:
        body = _remat(cfg, body)
    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (stacked_params, caches))
    return x, aux, new_caches


def decoder_stack_apply(params, cfg, x, positions, caches=None, window: int = 0,
                        use_flash: bool = False, moe_dense_ref: bool = False,
                        kv_valid=None, paged_kernel: bool = False):
    aux_total = jnp.zeros((), jnp.float32)
    dense_caches_new = []
    if "dense_layers" in params:
        for i, p_l in enumerate(params["dense_layers"]):
            c = None if caches is None else caches["dense"][i]
            x, nc, a = decoder_layer_apply(p_l, cfg, x, positions, cache=c,
                                           window=window, use_flash=use_flash,
                                           kv_valid=kv_valid,
                                           paged_kernel=paged_kernel)
            aux_total = aux_total + a
            dense_caches_new.append(nc)

    stack_caches = None if caches is None else caches["stack"]

    def layer_fn(p_l, x, cache_l):
        return decoder_layer_apply(p_l, cfg, x, positions, cache=cache_l,
                                   window=window, use_flash=use_flash,
                                   moe_dense_ref=moe_dense_ref,
                                   kv_valid=kv_valid,
                                   paged_kernel=paged_kernel)

    if cfg.scan_layers:
        x, aux, new_stack = _scan_layers(params["layers"], cfg, x, layer_fn,
                                         caches=stack_caches)
    else:
        fn = _remat(cfg, layer_fn) if cfg.remat else layer_fn
        n = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        new_list, aux = [], jnp.zeros((), jnp.float32)
        for i in range(n):
            p_l = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            c_l = (None if stack_caches is None
                   else jax.tree_util.tree_map(lambda a: a[i], stack_caches))
            x, nc, a = fn(p_l, x, c_l)
            new_list.append(nc)
            aux = aux + a
        new_stack = (None if stack_caches is None else
                     jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *new_list))
    aux_total = aux_total + aux

    new_caches = None
    if caches is not None:
        new_caches = {"stack": new_stack}
        if "dense_layers" in params:
            new_caches["dense"] = dense_caches_new
    return x, aux_total, new_caches


# =============================================================== decoder LM
def lm_specs(cfg) -> dict:
    specs = {
        "embedding": L.embedding_specs(cfg),
        **decoder_stack_specs(cfg),
        "final_norm": L.rmsnorm_specs(cfg.d_model),
    }
    if cfg.family == "vlm":
        specs["prefix_proj"] = ParamSpec((PREFIX_EMBED_DIM, cfg.d_model),
                                         (None, "embed_p"), init="scaled")
    return specs


def lm_apply(params, cfg, tokens, positions=None, prefix_embeds=None,
             caches=None, window: int = 0, use_flash: bool = False,
             moe_dense_ref: bool = False, kv_valid=None, return_hidden=False,
             last_token_only=False, paged_kernel: bool = False):
    """Decoder LM forward.  Returns (logits, aux, new_caches[, hidden]).

    ``last_token_only`` unembeds just the final position (serving prefill:
    avoids materializing (B,S,V) logits)."""
    x = L.embed_tokens(params["embedding"], cfg, tokens)
    if prefix_embeds is not None:
        pfx = (prefix_embeds.astype(cfg.activation_dtype)
               @ params["prefix_proj"].astype(cfg.activation_dtype))
        x = jnp.concatenate([pfx, x], axis=1)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x, aux, new_caches = decoder_stack_apply(
        params, cfg, x, positions, caches=caches, window=window,
        use_flash=use_flash, moe_dense_ref=moe_dense_ref, kv_valid=kv_valid,
        paged_kernel=paged_kernel)
    if last_token_only:
        x = x[:, -1:]
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embedding"] if cfg.tie_embeddings else
                       {**params["embedding"]}, cfg, x)
    if return_hidden:
        return logits, aux, new_caches, x
    return logits, aux, new_caches


# =============================================================== Mamba2 LM
def mamba_lm_specs(cfg) -> dict:
    layer = {"ln": L.rmsnorm_specs(cfg.d_model), "mamba": SSM.mamba_specs(cfg)}
    return {
        "embedding": L.embedding_specs(cfg),
        "layers": stack_specs(layer, cfg.n_layers, "layers"),
        "final_norm": L.rmsnorm_specs(cfg.d_model),
    }


def mamba_lm_apply(params, cfg, tokens, positions=None, caches=None,
                   use_kernel: bool = False, kv_valid=None,
                   last_token_only=False, **_):
    x = L.embed_tokens(params["embedding"], cfg, tokens)

    def layer_fn(p_l, x, cache_l):
        h, nc = SSM.mamba_apply(p_l["mamba"], cfg,
                                L.rmsnorm(p_l["ln"], x, cfg.norm_eps),
                                cache=cache_l, use_kernel=use_kernel,
                                kv_valid=kv_valid)
        return x + h, nc, jnp.zeros((), jnp.float32)

    stack_caches = None if caches is None else caches["stack"]
    if cfg.scan_layers:
        x, aux, new_stack = _scan_layers(params["layers"], cfg, x, layer_fn,
                                         caches=stack_caches)
    else:
        fn = _remat(cfg, layer_fn) if cfg.remat else layer_fn
        n = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        new_list = []
        aux = jnp.zeros((), jnp.float32)
        for i in range(n):
            p_l = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            c_l = (None if stack_caches is None else
                   jax.tree_util.tree_map(lambda a: a[i], stack_caches))
            x, nc, a = fn(p_l, x, c_l)
            new_list.append(nc)
        new_stack = (None if stack_caches is None else
                     jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *new_list))

    if last_token_only:
        x = x[:, -1:]
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embedding"], cfg, x)
    new_caches = None if caches is None else {"stack": new_stack}
    return logits, jnp.zeros((), jnp.float32), new_caches


# =============================================================== Zamba2 hybrid
def zamba_specs(cfg) -> dict:
    """G groups of (attn_every mamba layers) + one shared attn/mlp block with
    per-invocation LoRA (rank cfg.lora_rank) on q/k/v."""
    G = cfg.n_layers // cfg.attn_every
    mamba_layer = {"ln": L.rmsnorm_specs(cfg.d_model), "mamba": SSM.mamba_specs(cfg)}
    r, d, H, hd = cfg.lora_rank, cfg.d_model, cfg.n_heads, cfg.head_dim
    lora = {
        "qA": ParamSpec((d, r), ("embed_p", None), init="scaled"),
        "qB": ParamSpec((r, H, hd), (None, "heads", None), init="zeros"),
        "kA": ParamSpec((d, r), ("embed_p", None), init="scaled"),
        "kB": ParamSpec((r, cfg.n_kv_heads, hd), (None, "kv_heads", None), init="zeros"),
        "vA": ParamSpec((d, r), ("embed_p", None), init="scaled"),
        "vB": ParamSpec((r, cfg.n_kv_heads, hd), (None, "kv_heads", None), init="zeros"),
    }
    return {
        "embedding": L.embedding_specs(cfg),
        "mamba_layers": stack_specs(stack_specs(mamba_layer, cfg.attn_every),
                                    G, "layers"),
        "shared": decoder_layer_specs(cfg, moe_layer=False),
        "lora": stack_specs(lora, G, "layers"),
        "final_norm": L.rmsnorm_specs(cfg.d_model),
    }


def _lora_adjusted(shared_attn: dict, lora_g: dict) -> dict:
    p = dict(shared_attn)
    for name in ("q", "k", "v"):
        delta = jnp.einsum("dr,rhe->dhe", lora_g[f"{name}A"].astype(p[name].dtype),
                           lora_g[f"{name}B"].astype(p[name].dtype))
        p[name] = p[name] + delta
    return p


def zamba_apply(params, cfg, tokens, positions=None, caches=None,
                window: int = 0, use_flash: bool = False, use_kernel: bool = False,
                kv_valid=None, last_token_only=False,
                paged_kernel: bool = False, **_):
    x = L.embed_tokens(params["embedding"], cfg, tokens)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    k = cfg.attn_every

    def group_fn(xs_g, x, cache_g):
        p_g, lora_g = xs_g
        mamba_caches = None if cache_g is None else cache_g["mamba"]

        def inner(p_l, x, c_l):
            h, nc = SSM.mamba_apply(p_l["mamba"], cfg,
                                    L.rmsnorm(p_l["ln"], x, cfg.norm_eps),
                                    cache=c_l, use_kernel=use_kernel,
                                    kv_valid=kv_valid)
            return x + h, nc, jnp.zeros((), jnp.float32)

        def inner_body(carry, xs):
            x = carry
            p_l, c_l = xs
            x, nc, _ = inner(p_l, x, c_l)
            return x, nc

        if cfg.scan_layers:
            x, new_mamba = jax.lax.scan(inner_body, x, (p_g, mamba_caches))
        else:
            k_in = jax.tree_util.tree_leaves(p_g)[0].shape[0]
            inner_list = []
            for j in range(k_in):
                p_l = jax.tree_util.tree_map(lambda a: a[j], p_g)
                c_l = (None if mamba_caches is None else
                       jax.tree_util.tree_map(lambda a: a[j], mamba_caches))
                x, nc = inner_body(x, (p_l, c_l))
                inner_list.append(nc)
            new_mamba = (None if mamba_caches is None else
                         jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                                *inner_list))

        # shared attention block with this group's LoRA
        shared = dict(params["shared"])
        shared_attn = _lora_adjusted(params["shared"]["attn"], lora_g)
        attn_cache = None if cache_g is None else cache_g["attn"]
        h, new_attn_cache = attn.attention_apply(
            shared_attn, cfg, L.rmsnorm(shared["ln1"], x, cfg.norm_eps),
            positions, cache=attn_cache, window=window, use_flash=use_flash,
            kv_valid=kv_valid, paged_kernel=paged_kernel)
        x = x + h
        x = x + L.mlp(shared["mlp"], L.rmsnorm(shared["ln2"], x, cfg.norm_eps))
        new_cache = (None if cache_g is None
                     else {"mamba": new_mamba, "attn": new_attn_cache})
        return x, new_cache, jnp.zeros((), jnp.float32)

    def body(carry, xs):
        x = carry
        (p_g, lora_g), cache_g = xs
        x, nc, _ = group_fn((p_g, lora_g), x, cache_g)
        return x, nc

    if cfg.remat:
        body = _remat(cfg, body)
    stack_caches = None if caches is None else caches["stack"]
    if cfg.scan_layers:
        x, new_stack = jax.lax.scan(
            body, x, ((params["mamba_layers"], params["lora"]), stack_caches))
    else:
        G = jax.tree_util.tree_leaves(params["mamba_layers"])[0].shape[0]
        new_list = []
        for g in range(G):
            xs_g = jax.tree_util.tree_map(
                lambda a: a[g], (params["mamba_layers"], params["lora"]))
            c_g = (None if stack_caches is None else
                   jax.tree_util.tree_map(lambda a: a[g], stack_caches))
            x, nc = body(x, (xs_g, c_g))
            new_list.append(nc)
        new_stack = (None if stack_caches is None else
                     jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                            *new_list))

    if last_token_only:
        x = x[:, -1:]
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embedding"], cfg, x)
    new_caches = None if caches is None else {"stack": new_stack}
    return logits, jnp.zeros((), jnp.float32), new_caches


# =============================================================== enc-dec
def encdec_specs(cfg) -> dict:
    enc_layer = {
        "ln1": L.rmsnorm_specs(cfg.d_model),
        "attn": attn.attention_specs(cfg),
        "ln2": L.rmsnorm_specs(cfg.d_model),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff),
    }
    dec_layer = {
        "ln1": L.rmsnorm_specs(cfg.d_model),
        "attn": attn.attention_specs(cfg),
        "ln_x": L.rmsnorm_specs(cfg.d_model),
        "xattn": attn.cross_attention_specs(cfg),
        "ln2": L.rmsnorm_specs(cfg.d_model),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff),
    }
    return {
        "embedding": L.embedding_specs(cfg),
        "frontend_proj": ParamSpec((PREFIX_EMBED_DIM, cfg.d_model),
                                   (None, "embed_p"), init="scaled"),
        "enc_layers": stack_specs(enc_layer, cfg.n_encoder_layers, "layers"),
        "enc_norm": L.rmsnorm_specs(cfg.d_model),
        "dec_layers": stack_specs(dec_layer, cfg.n_layers, "layers"),
        "final_norm": L.rmsnorm_specs(cfg.d_model),
    }


def encdec_encode(params, cfg, prefix_embeds, use_flash: bool = False):
    """Frame/patch embeddings (B,M,PREFIX_EMBED_DIM) -> encoder output (B,M,d)."""
    dt = cfg.activation_dtype
    x = prefix_embeds.astype(dt) @ params["frontend_proj"].astype(dt)
    B, M, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32), (B, M))

    def body(carry, p_l):
        x = carry
        h, _ = attn.attention_apply(p_l["attn"], cfg,
                                    L.rmsnorm(p_l["ln1"], x, cfg.norm_eps),
                                    positions, causal=False)
        x = x + h
        x = x + L.mlp(p_l["mlp"], L.rmsnorm(p_l["ln2"], x, cfg.norm_eps))
        return x, None

    if cfg.remat:
        body = _remat(cfg, body)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params["enc_layers"])
    else:
        n = jax.tree_util.tree_leaves(params["enc_layers"])[0].shape[0]
        for i in range(n):
            x, _ = body(x, jax.tree_util.tree_map(lambda a: a[i],
                                                  params["enc_layers"]))
    return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def encdec_cross_kv(params, cfg, enc_out):
    """Precompute per-decoder-layer cross K/V (stacked over layers)."""
    def body(_, p_l):
        return None, attn.encode_cross_kv(p_l["xattn"], cfg, enc_out)
    if cfg.scan_layers:
        _, kv = jax.lax.scan(body, None, params["dec_layers"])
        return kv  # (k,v) each (L,B,M,Hk,hd)
    n = jax.tree_util.tree_leaves(params["dec_layers"])[0].shape[0]
    kvs = [body(None, jax.tree_util.tree_map(lambda a: a[i],
                                             params["dec_layers"]))[1]
           for i in range(n)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *kvs)


def encdec_decode_stack(params, cfg, tokens, cross_kv, positions=None,
                        caches=None, window: int = 0, use_flash: bool = False,
                        kv_valid=None, last_token_only=False,
                        paged_kernel: bool = False):
    x = L.embed_tokens(params["embedding"], cfg, tokens)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(carry, xs):
        x = carry
        p_l, kv_l, cache_l = xs
        h, nc = attn.attention_apply(p_l["attn"], cfg,
                                     L.rmsnorm(p_l["ln1"], x, cfg.norm_eps),
                                     positions, cache=cache_l, window=window,
                                     use_flash=use_flash, kv_valid=kv_valid,
                                     paged_kernel=paged_kernel)
        x = x + h
        x = x + attn.cross_attention_apply(p_l["xattn"], cfg,
                                           L.rmsnorm(p_l["ln_x"], x, cfg.norm_eps),
                                           kv_l)
        x = x + L.mlp(p_l["mlp"], L.rmsnorm(p_l["ln2"], x, cfg.norm_eps))
        return x, nc

    if cfg.remat:
        body = _remat(cfg, body)
    stack_caches = None if caches is None else caches["stack"]
    if cfg.scan_layers:
        x, new_stack = jax.lax.scan(
            body, x, (params["dec_layers"], cross_kv, stack_caches))
    else:
        n = jax.tree_util.tree_leaves(params["dec_layers"])[0].shape[0]
        new_list = []
        for i in range(n):
            xs_i = jax.tree_util.tree_map(
                lambda a: a[i], (params["dec_layers"], cross_kv, stack_caches))
            x, nc = body(x, xs_i)
            new_list.append(nc)
        new_stack = (None if stack_caches is None else
                     jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                            *new_list))
    if last_token_only:
        x = x[:, -1:]
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embedding"], cfg, x)
    new_caches = None if caches is None else {"stack": new_stack}
    return logits, jnp.zeros((), jnp.float32), new_caches


def encdec_apply(params, cfg, tokens, prefix_embeds=None, positions=None,
                 caches=None, window: int = 0, use_flash: bool = False,
                 kv_valid=None, last_token_only=False,
                 paged_kernel: bool = False, **_):
    enc_out = encdec_encode(params, cfg, prefix_embeds, use_flash=use_flash)
    cross_kv = encdec_cross_kv(params, cfg, enc_out)
    return encdec_decode_stack(params, cfg, tokens, cross_kv,
                               positions=positions, caches=caches,
                               window=window, use_flash=use_flash,
                               kv_valid=kv_valid,
                               last_token_only=last_token_only,
                               paged_kernel=paged_kernel)
