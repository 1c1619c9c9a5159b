"""Grouped-query attention with RoPE, qk-norm, sliding-window / chunked
masks, KV-cache decode, cross-attention, and bidirectional (encoder) mode.

The XLA path here is the baseline; :mod:`repro.kernels.flash_attention` is
the Pallas TPU fast path selected via ``attn_impl='pallas'``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, rmsnorm
from repro.models.module import ParamBuilder
from repro.sharding.partitioning import constrain

NEG_INF = -2.3819763e38  # close to bf16 min, used by flash implementations
GLOBAL_WINDOW = 2 ** 30  # 'window' large enough to mean full attention


def init_attention(b: ParamBuilder, cfg: ModelConfig,
                   stacked: int | None = None) -> None:
    d, h, kh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    lead = (stacked,) if stacked else ()
    lax_ = ("layers",) if stacked else ()
    b.add("wq", lead + (d, h, hd), lax_ + ("embed", "heads", "head_dim"))
    b.add("wk", lead + (d, kh, hd), lax_ + ("embed", "kv_heads", "head_dim"))
    b.add("wv", lead + (d, kh, hd), lax_ + ("embed", "kv_heads", "head_dim"))
    b.add("wo", lead + (h, hd, d), lax_ + ("heads", "head_dim", "embed"))
    if cfg.qk_norm:
        b.add("q_norm", lead + (hd,), lax_ + ("norm",), init="ones")
        b.add("k_norm", lead + (hd,), lax_ + ("norm",), init="ones")


def _project_qkv(params: dict, x: jax.Array, cfg: ModelConfig,
                 positions: jax.Array, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))
    return q, k, v


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, window, chunk,
               causal: bool = True) -> jax.Array:
    """Additive bias [q_len, k_len] in f32 from position vectors."""
    dq = q_pos[:, None]
    dk = k_pos[None, :]
    ok = jnp.ones(dq.shape[:1] + dk.shape[1:], jnp.bool_)
    if causal:
        ok &= dk <= dq
    if window is not None:
        ok &= (dq - dk) < window
    if chunk is not None:
        ok &= (dq // chunk) == (dk // chunk)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(q, k, v, bias, cfg: ModelConfig):
    """q:[B,Sq,H,hd] k,v:[B,Sk,KH,hd] bias:[Sq,Sk] (or [B,1,Sq,Sk])."""
    b_, sq, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    q = q.reshape(b_, sq, kh, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if bias.ndim == 2:
        scores = scores + bias[None, None, None]
    else:
        scores = scores + bias[:, :, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    out = out.reshape(b_, sq, h, hd)
    return constrain(out, ("batch", "seq", "heads", None))


def _sdpa_qblocked(q, k, v, q_pos, k_pos, window, chunk, causal,
                   cfg: ModelConfig, block: int):
    """Exact attention scanned over query blocks.

    Materializing [B,H,Sq,Sk] scores at 4k-32k sequence lengths needs
    terabytes; scanning q-blocks keeps live memory to one [B,H,block,Sk]
    slab.  The block body is remat'd so backward recomputes scores instead
    of saving every block (activation-checkpoint policy, DESIGN.md).
    """
    b_, sq, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    nb = sq // block
    qb = q.reshape(b_, nb, block, kh, g, hd).transpose(1, 0, 2, 3, 4, 5)
    pb = q_pos.reshape(nb, block)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    @jax.checkpoint
    def body(carry, xs):
        qblk, pblk = xs                      # [B, blk, KH, G, hd], [blk]
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qblk, k).astype(jnp.float32)
        scores = scores * scale
        scores = scores + _mask_bias(pblk, k_pos, window, chunk, causal)[
            None, None, None]
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
        return carry, out

    _, outs = jax.lax.scan(body, None, (qb, pb))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b_, sq, h, hd)
    return constrain(out, ("batch", "seq", "heads", None))


def mha_full(params: dict, x: jax.Array, cfg: ModelConfig,
             positions: jax.Array, window=None, chunk=None,
             causal: bool = True, q_block: int | None = None) -> jax.Array:
    """Full-sequence self attention (training / prefill)."""
    q, k, v = _project_qkv(params, x, cfg, positions, rope=not _no_rope(cfg))
    return _self_attend(params, q, k, v, cfg, positions, window, chunk,
                        causal, q_block)


def mha_prefill(params: dict, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array, cache_dtype):
    """Causal self attention over a whole prompt, with no window or chunk,
    that also returns its keys and values for the decode cache.

    x:[B,S,d].  Returns (y, k, v), k/v [B,S,KH,hd] in ``cache_dtype``, for
    the caller to write at positions ``0..S-1``: the qk-norm, RoPE and
    rounding of :func:`mha_decode_append`, and the attention reads them as
    the decode steps after it will, from the cache's dtype."""
    q, k, v = _project_qkv(params, x, cfg, positions, rope=not _no_rope(cfg))
    k, v = k.astype(cache_dtype), v.astype(cache_dtype)
    y = _self_attend(params, q, k.astype(q.dtype), v.astype(q.dtype), cfg,
                     positions)
    return y, k, v


def _self_attend(params, q, k, v, cfg: ModelConfig, positions, window=None,
                 chunk=None, causal: bool = True, q_block: int | None = None):
    q_block = q_block or cfg.attn_q_block
    s = q.shape[1]
    pos = positions[0] if positions.ndim > 1 else positions
    static_window = isinstance(window, int) or window is None
    if (cfg.attn_impl == "pallas" and chunk is None and causal
            and static_window):
        from repro.kernels.ops import flash_mha
        out = flash_mha(q, k, v, causal=True, window=window)
    elif s <= q_block or s % q_block != 0:
        bias = _mask_bias(pos, pos, window, chunk, causal)
        out = _sdpa(q, k, v, bias, cfg)
    else:
        out = _sdpa_qblocked(q, k, v, pos, pos, window, chunk, causal, cfg,
                             q_block)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return constrain(y, ("batch", "seq", None))


def _window_valid(k_pos: jax.Array, index: jax.Array, window, chunk
                  ) -> jax.Array:
    """Which cached positions the sliding-window and chunk terms let the
    token at ``index`` see; the causal term is the caller's."""
    valid = jnp.ones(k_pos.shape, jnp.bool_)
    if window is not None:
        valid &= (index - k_pos) < window
    if chunk is not None:
        valid &= (k_pos // chunk) == (index // chunk)
    return valid


def mha_decode_append(params: dict, x: jax.Array, cfg: ModelConfig,
                      cache_k: jax.Array, cache_v: jax.Array,
                      index: jax.Array, window=None, chunk=None):
    """One-token decode that leaves the cache to the caller.

    x:[B,1,d]; cache_k/v:[B,C,KH,hd], read only: positions ``< index`` are
    attended from the cache and the token at ``index`` from its own k/v,
    so nothing cache-sized is written here.  Returns (y, k_new, v_new),
    k_new/v_new [B,1,KH,hd] in the cache's dtype, for the caller to write
    at ``index`` (:func:`write_positions`).  Scores and softmax are f32;
    the new token is rounded through the cache's dtype, as it would be had
    it been read back from the cache.
    """
    positions = jnp.full((x.shape[0], 1), index, jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, cfg, positions,
                                   rope=not _no_rope(cfg))
    k_new = k_new.astype(cache_k.dtype)
    v_new = v_new.astype(cache_v.dtype)
    b_, _, h, hd = q.shape
    c, kh = cache_k.shape[1], cache_k.shape[2]
    dt, f32 = q.dtype, jnp.float32
    k_pos = jnp.arange(c)
    valid = (k_pos < index) & _window_valid(k_pos, index, window, chunk)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(f32)
    qg = q.reshape(b_, kh, h // kh, hd)
    scale = jnp.sqrt(jnp.asarray(hd, f32))
    s_cache = jnp.einsum("bkgd,bskd->bkgs", qg, cache_k.astype(dt)
                         ).astype(f32) / scale + bias
    s_new = jnp.einsum("bkgd,bkd->bkg", qg, k_new[:, 0].astype(dt)
                       ).astype(f32) / scale
    probs = jax.nn.softmax(
        jnp.concatenate([s_cache, s_new[..., None]], axis=-1), axis=-1
    ).astype(dt)
    out = (jnp.einsum("bkgs,bskd->bkgd", probs[..., :c], cache_v.astype(dt),
                      preferred_element_type=f32)
           + jnp.einsum("bkg,bkd->bkgd", probs[..., c], v_new[:, 0].astype(dt),
                        preferred_element_type=f32))
    out = constrain(out.astype(dt).reshape(b_, 1, h, hd),
                    ("batch", "seq", "heads", None))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return constrain(y, ("batch", "seq", None)), k_new, v_new


def write_positions(caches: dict, new: dict, index) -> dict:
    """``caches`` with each stack of ``new``, [L, B, S, KH, hd], written
    at positions ``index..index+S-1`` of the stacked [L, B, C, KH, hd]
    cache of that name; the other caches as they are.

    The layer scans read the caches and return only the positions they
    add (one, in a decode step); the donated stacks then take them in
    place.  A scan that returned whole layer caches would restack (copy)
    all of them on every step."""
    upd = jax.lax.dynamic_update_slice_in_dim
    return {**caches, **{name: upd(caches[name], pos, index, axis=2)
                         for name, pos in new.items()}}


def _attend(q, k, v, q_pos, k_pos, window, chunk, causal, cfg,
            q_block: int = 512):
    sq = q.shape[1]
    if sq <= q_block or sq % q_block != 0:
        bias = _mask_bias(q_pos, k_pos, window, chunk, causal)
        return _sdpa(q, k, v, bias, cfg)
    return _sdpa_qblocked(q, k, v, q_pos, k_pos, window, chunk, causal,
                          cfg, q_block)


def mha_cross(params: dict, x: jax.Array, enc_k: jax.Array,
              enc_v: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Cross attention (whisper decoder): K/V precomputed from encoder."""
    b_, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
    q_pos = jnp.arange(s)
    k_pos = jnp.arange(enc_k.shape[1])
    out = _attend(q, enc_k, enc_v, q_pos, k_pos, None, None, False, cfg)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return constrain(y, ("batch", "seq", None))


def cross_kv(params: dict, enc_out: jax.Array, cfg: ModelConfig):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"])
    if cfg.qk_norm:
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    return k, v


def mha_bidirectional(params: dict, x: jax.Array, cfg: ModelConfig
                      ) -> jax.Array:
    """Encoder self-attention: no mask, no cache (whisper encoder uses
    learned positional embeddings added by the caller, so no RoPE)."""
    b_, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b_, s))
    q, k, v = _project_qkv(params, x, cfg, positions, rope=False)
    pos = jnp.arange(s)
    out = _attend(q, k, v, pos, pos, None, None, False, cfg)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return constrain(y, ("batch", "seq", None))


def _no_rope(cfg: ModelConfig) -> bool:
    return cfg.family == "audio"  # whisper uses learned positions


def init_kv_cache(cfg: ModelConfig, n_layers: int, batch: int, context: int,
                  dtype=jnp.bfloat16):
    """Stacked [L, B, C, KH, hd] caches for scan-over-layers decode."""
    kh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (n_layers, batch, context, kh, hd)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
