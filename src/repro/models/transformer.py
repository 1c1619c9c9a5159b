"""Decoder-only transformer stacks for the dense / MoE / VLM / SSM families.

Layers are scan-stacked (params carry a leading ``layers`` axis) so 48-81
layer models compile quickly; per-layer attention patterns (gemma3's 5
local : 1 global, llama4's chunked iRoPE) ride along the scan as traced
window/chunk vectors.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (embed_tokens, init_embedding, init_mlp,
                                 init_rmsnorm, mlp, rmsnorm, unembed)
from repro.models.module import ParamBuilder

GLOBAL = attn.GLOBAL_WINDOW


def layer_pattern(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer window sizes: GLOBAL for global layers, the local window
    (sliding or chunk) otherwise — consumed as traced scan inputs."""
    win = []
    for i in range(cfg.n_layers):
        if cfg.layer_is_global(i):
            win.append(GLOBAL)
        elif cfg.sliding_window is not None:
            win.append(cfg.sliding_window)
        elif cfg.attention_chunk is not None:
            win.append(cfg.attention_chunk)
        else:
            win.append(GLOBAL)
    return jnp.asarray(win, jnp.int32)


def chunked_flags(cfg: ModelConfig) -> bool:
    return cfg.attention_chunk is not None


def all_layers_global(cfg: ModelConfig) -> bool:
    """No local layers: every layer's window is statically "none", so the
    full-sequence attention may take the flash kernel, whose window must be
    static (a traced per-layer window keeps it on the XLA path)."""
    return cfg.sliding_window is None and cfg.attention_chunk is None


def remat_layer(fn):
    """Per-layer activation checkpointing: inside a scanned stack only the
    inter-layer carry is saved; everything else recomputes in backward.
    This is the baseline checkpoint policy (DESIGN.md) — without it a
    62-layer 4k-seq step saves every per-layer intermediate and blows HBM."""
    import functools
    return functools.partial(jax.checkpoint, prevent_cse=False)(fn)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DecoderOutput:
    logits: jax.Array
    aux_loss: jax.Array
    #: the decode caches a prefill wrote (:func:`forward`'s ``caches``)
    caches: dict | None = None


# -- init ---------------------------------------------------------------------------

def init_decoder(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    b = ParamBuilder(key)
    init_embedding(b, cfg)
    lyr = b.sub("layers")
    L = cfg.n_layers
    if cfg.family == "ssm":
        ssm_lib.init_ssm(lyr, cfg, stacked=L)
        init_rmsnorm_stacked(lyr, "norm1", cfg.d_model, L)
    else:
        attn.init_attention(lyr, cfg, stacked=L)
        init_rmsnorm_stacked(lyr, "norm1", cfg.d_model, L)
        init_rmsnorm_stacked(lyr, "norm2", cfg.d_model, L)
        if cfg.n_experts and cfg.moe_every == 1:
            moe_lib.init_moe(lyr, cfg, stacked=L)
        elif cfg.n_experts:
            # alternating dense/MoE (llama4): separate stacked sub-trees
            n_moe = L // cfg.moe_every
            n_dense = L - n_moe
            moe_lib.init_moe(b.sub("moe_layers"), cfg, stacked=n_moe)
            init_mlp(b.sub("dense_layers"), cfg,
                     d_ff=cfg.d_ff * cfg.moe_every, stacked=n_dense)
        else:
            init_mlp(lyr, cfg, stacked=L)
    init_rmsnorm(b, "final_norm", cfg.d_model)
    return b.build()


def init_rmsnorm_stacked(b: ParamBuilder, name: str, dim: int, L: int):
    b.add(name, (L, dim), ("layers", "norm"), init="ones")


# -- forward (train / prefill) ---------------------------------------------------

def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            extra_embeddings: jax.Array | None = None,
            last_only: bool = False, caches: dict | None = None,
            length: jax.Array | None = None) -> DecoderOutput:
    """tokens: [B,S] int32. extra_embeddings: [B,V,d] stub frontend output
    (VLM patches) overriding the first V positions.

    With ``caches``, as :func:`init_caches` made them for a layout
    :func:`prefill_length` accepts, this is the serving prefill:
    ``DecoderOutput.caches`` holds them with the prompt's positions
    written.  ``length`` (a scalar) is where the prompt ends in ``tokens``:
    ``last_only`` takes the logits at ``length - 1``, and the keys and
    values of the pads after it are written as zeros (an SSM takes no
    pads)."""
    b_, s = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    dtype = x.dtype
    if extra_embeddings is not None:
        v = extra_embeddings.shape[1]
        x = jnp.concatenate([extra_embeddings.astype(x.dtype), x[:, v:]],
                            axis=1)
    positions = jnp.broadcast_to(jnp.arange(s), (b_, s))
    windows = layer_pattern(cfg)
    is_chunked = chunked_flags(cfg)
    no_window = is_chunked or all_layers_global(cfg)

    if cfg.family == "ssm":
        @remat_layer
        def ssm_body(h, lp):
            out, xbc, state = ssm_lib.ssm_forward(
                lp, _ssm_input(h, lp, cfg, dtype), cfg)
            if caches is None:
                return h + out, None
            c = caches["ssm"]
            return h + out, {
                "conv": ssm_lib.conv_cache(xbc, cfg, c["conv"].dtype),
                "state": state.astype(c["state"].dtype)}

        x, ssm_caches = jax.lax.scan(ssm_body, _residual(x, cfg),
                                     params["layers"])
        if caches is not None:
            caches = {"ssm": ssm_caches}
        aux = jnp.zeros((), jnp.float32)
    elif cfg.n_experts and cfg.moe_every > 1:
        x, aux = _forward_interleaved_moe(params, cfg, x, positions, windows)
    else:
        @remat_layer
        def body(carry, xs):
            h, aux = carry
            lp, win = xs
            window = jnp.where(win >= GLOBAL, jnp.int32(2 ** 30), win)
            chunk = window if is_chunked else None
            w_arg = None if no_window else window
            hn = rmsnorm(h, lp["norm1"], cfg.norm_eps)
            if caches is None:
                h = h + attn.mha_full(lp, hn, cfg, positions, window=w_arg,
                                      chunk=chunk)
                kv = None
            else:           # every layer global: prefill_length
                out, k, v = attn.mha_prefill(lp, hn, cfg, positions,
                                             caches["k"].dtype)
                h = h + out
                pad = (positions >= length)[..., None, None]
                kv = (jnp.where(pad, 0, k), jnp.where(pad, 0, v))
            hn = rmsnorm(h, lp["norm2"], cfg.norm_eps)
            if cfg.n_experts:
                out, a = moe_lib.moe_layer(lp, hn, cfg)
                aux = aux + a
            else:
                out = mlp(lp, hn, cfg)
            h = h + out
            return (h, aux), kv

        (x, aux), kv = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["layers"], windows))
        if caches is not None:
            # [L, B, S, KH, hd] each, into the donated stacks at position 0;
            # positions past the cache are pads
            c = caches["k"].shape[2]
            caches = attn.write_positions(
                caches, {name: new[:, :, :c]
                         for name, new in zip(("k", "v"), kv)}, 0)

    if last_only:
        x = x[:, -1:] if length is None else \
            jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps).astype(dtype)
    logits = unembed(params, x, cfg)
    return DecoderOutput(logits=logits, aux_loss=aux, caches=caches)


def _residual(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The residual stream: float32 with ``residual_in_fp32``, else the
    embedding's dtype."""
    return x.astype(jnp.float32) if cfg.residual_in_fp32 else x


def _ssm_input(h: jax.Array, lp: dict, cfg: ModelConfig, dtype) -> jax.Array:
    """A layer's normed input in the weights' dtype: mamba_ssm's fused
    add+norm, which normalises the float32 residual and hands the mixer its
    matmuls' dtype."""
    return rmsnorm(h, lp["norm1"], cfg.norm_eps).astype(dtype)


def _forward_interleaved_moe(params, cfg, x, positions, windows):
    """llama4-style: layer i is MoE iff (i+1) % moe_every == 0; the stacks
    are scanned separately in interleaved order via two scans per pair."""
    L = cfg.n_layers
    m = cfg.moe_every
    n_pairs = L // m
    is_chunked = chunked_flags(cfg)
    # reshape stacked params into [n_pairs, ...] chunks
    dense = params["dense_layers"]
    moe_p = params["moe_layers"]
    lyr = params["layers"]

    @remat_layer
    def pair_body(carry, xs):
        h, aux = carry
        lp_group, dense_group, moe_lp, win_group = xs

        # (m-1) dense layers then 1 MoE layer, all attention-bearing
        def inner(carry2, xs2):
            h2 = carry2
            lp, dlp, win = xs2
            window = jnp.where(win >= GLOBAL, jnp.int32(2 ** 30), win)
            chunk = window if is_chunked else None
            w_arg = None if is_chunked else window
            h2 = h2 + attn.mha_full(
                lp, rmsnorm(h2, lp["norm1"], cfg.norm_eps), cfg, positions,
                window=w_arg, chunk=chunk)
            h2 = h2 + mlp(dlp, rmsnorm(h2, lp["norm2"], cfg.norm_eps), cfg)
            return h2, None

        if m > 1:
            h, _ = jax.lax.scan(
                inner, h,
                (jax.tree_util.tree_map(lambda a: a[:m - 1], lp_group),
                 dense_group,
                 win_group[:m - 1]))
        lp_last = jax.tree_util.tree_map(lambda a: a[m - 1], lp_group)
        win = win_group[m - 1]
        window = jnp.where(win >= GLOBAL, jnp.int32(2 ** 30), win)
        chunk = window if is_chunked else None
        w_arg = None if is_chunked else window
        h = h + attn.mha_full(
            lp_last, rmsnorm(h, lp_last["norm1"], cfg.norm_eps), cfg,
            positions, window=w_arg, chunk=chunk)
        out, a = moe_lib.moe_layer(
            moe_lp, rmsnorm(h, lp_last["norm2"], cfg.norm_eps), cfg)
        return (h + out, aux + a), None

    grouped = jax.tree_util.tree_map(
        lambda a: a.reshape((n_pairs, m) + a.shape[1:]), lyr)
    dense_grouped = jax.tree_util.tree_map(
        lambda a: a.reshape((n_pairs, m - 1) + a.shape[1:]), dense)
    win_grouped = windows.reshape(n_pairs, m)
    (x, aux), _ = jax.lax.scan(
        pair_body, (x, jnp.zeros((), jnp.float32)),
        (grouped, dense_grouped, moe_p, win_grouped))
    return x, aux


# -- decode ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, context: int) -> dict:
    if cfg.family == "ssm":
        return {"ssm": ssm_lib.init_ssm_cache(cfg, cfg.n_layers, batch)}
    k, v = attn.init_kv_cache(cfg, cfg.n_layers, batch, context)
    return {"k": k, "v": v}


def decode_step(params: dict, cfg: ModelConfig, token: jax.Array,
                index: jax.Array, caches: dict) -> tuple[jax.Array, dict]:
    """token: [B,1] int32; index: scalar int32 position.  Returns
    (logits [B,1,V], updated caches)."""
    x = embed_tokens(params, token, cfg)
    dtype = x.dtype
    windows = layer_pattern(cfg)
    is_chunked = chunked_flags(cfg)

    if cfg.family == "ssm":
        def body(carry, xs):
            h, ssm_c = carry
            lp, layer = xs
            out, ssm_c = ssm_lib.ssm_decode_layer(
                lp, _ssm_input(h, lp, cfg, dtype), ssm_c, layer, cfg)
            return (h + out, ssm_c), None

        (x, ssm_c), _ = jax.lax.scan(
            body, (_residual(x, cfg), caches["ssm"]),
            (params["layers"], jnp.arange(cfg.n_layers)))
        caches = {"ssm": ssm_c}
    elif cfg.n_experts and cfg.moe_every > 1:
        x, caches = _decode_interleaved_moe(params, cfg, x, index, caches,
                                            windows)
    else:
        def body(carry, xs):
            h = carry
            lp, ck, cv, win = xs
            window = jnp.where(win >= GLOBAL, jnp.int32(2 ** 30), win)
            chunk = window if is_chunked else None
            w_arg = None if is_chunked else window
            out, k_new, v_new = attn.mha_decode_append(
                lp, rmsnorm(h, lp["norm1"], cfg.norm_eps), cfg, ck, cv,
                index, window=w_arg, chunk=chunk)
            h = h + out
            hn = rmsnorm(h, lp["norm2"], cfg.norm_eps)
            if cfg.n_experts:
                out2, _ = moe_lib.moe_layer(lp, hn, cfg)
            else:
                out2 = mlp(lp, hn, cfg)
            return h + out2, (k_new, v_new)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], caches["k"], caches["v"], windows))
        caches = attn.write_positions(caches, {"k": k_new, "v": v_new}, index)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps).astype(dtype)
    logits = unembed(params, x, cfg)
    return logits, caches


def prefill_length(cfg: ModelConfig, s: int) -> int | None:
    """The positions one prefill call (:func:`forward` with ``caches``)
    takes for an ``s``-position prompt, or None where the prompt is
    replayed through :func:`decode_step` one position at a time.

    The prefill writes two layouts: an SSM's off the Pallas path, which
    returns no state, taken at ``s`` positions since a pad would enter the
    state; and the KV caches behind the plain layer scan of a dense decoder
    with no experts whose every layer attends globally, padded up to a
    multiple of 128 positions, or of ``attn_q_block`` past it, where
    attention scans query blocks instead of holding the whole [S, S]
    scores: a few programs serve every prompt length.  Expert routing,
    windows, chunks, patch embeddings, the hybrid and the encoder-decoder
    keep the replay."""
    if cfg.family == "ssm":
        return s if cfg.ssm_impl != "pallas" else None
    if not (cfg.family == "dense" and not cfg.n_experts
            and all_layers_global(cfg)):
        return None
    step = cfg.attn_q_block if s > cfg.attn_q_block else 128
    return -(-s // step) * step


def _decode_interleaved_moe(params, cfg, x, index, caches, windows):
    """llama4's decode layers, in :func:`_forward_interleaved_moe`'s
    order.  Returns (x, caches with every layer's new position written)."""
    L, m = cfg.n_layers, cfg.moe_every
    n_pairs = L // m
    is_chunked = chunked_flags(cfg)
    lyr = params["layers"]
    grouped = jax.tree_util.tree_map(
        lambda a: a.reshape((n_pairs, m) + a.shape[1:]), lyr)
    dense_grouped = jax.tree_util.tree_map(
        lambda a: a.reshape((n_pairs, m - 1) + a.shape[1:]),
        params["dense_layers"])
    win_grouped = windows.reshape(n_pairs, m)
    k_grouped = caches["k"].reshape((n_pairs, m) + caches["k"].shape[1:])
    v_grouped = caches["v"].reshape((n_pairs, m) + caches["v"].shape[1:])

    def one_attn(h, lp, ck, cv, win):
        window = jnp.where(win >= GLOBAL, jnp.int32(2 ** 30), win)
        chunk = window if is_chunked else None
        w_arg = None if is_chunked else window
        out, k_new, v_new = attn.mha_decode_append(
            lp, rmsnorm(h, lp["norm1"], cfg.norm_eps), cfg, ck, cv, index,
            window=w_arg, chunk=chunk)
        return h + out, k_new, v_new

    def pair_body(carry, xs):
        h = carry
        lp_group, dense_group, moe_lp, win_group, ckg, cvg = xs

        def inner(h2, xs2):
            lp, dlp, win, ck, cv = xs2
            h2, k_new, v_new = one_attn(h2, lp, ck, cv, win)
            h2 = h2 + mlp(dlp, rmsnorm(h2, lp["norm2"], cfg.norm_eps), cfg)
            return h2, (k_new, v_new)

        ks, vs = [], []
        if m > 1:
            h, (k_new, v_new) = jax.lax.scan(
                inner, h,
                (jax.tree_util.tree_map(lambda a: a[:m - 1], lp_group),
                 dense_group, win_group[:m - 1], ckg[:m - 1], cvg[:m - 1]))
            ks, vs = [k_new], [v_new]
        lp_last = jax.tree_util.tree_map(lambda a: a[m - 1], lp_group)
        h, k_new, v_new = one_attn(h, lp_last, ckg[m - 1], cvg[m - 1],
                                   win_group[m - 1])
        out, _ = moe_lib.moe_layer(
            moe_lp, rmsnorm(h, lp_last["norm2"], cfg.norm_eps), cfg)
        # [m, B, 1, KH, hd]: one position per layer of the pair
        return h + out, (jnp.concatenate(ks + [k_new[None]], axis=0),
                         jnp.concatenate(vs + [v_new[None]], axis=0))

    x, (k_new, v_new) = jax.lax.scan(
        pair_body, x,
        (grouped, dense_grouped, params["moe_layers"], win_grouped,
         k_grouped, v_grouped))
    # [n_pairs, m, B, 1, KH, hd] -> [L, B, 1, KH, hd], layers in order
    return x, attn.write_positions(
        caches, {"k": k_new.reshape((L,) + k_new.shape[2:]),
                 "v": v_new.reshape((L,) + v_new.shape[2:])}, index)
