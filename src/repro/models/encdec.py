"""Whisper-style encoder-decoder (arXiv:2212.04356).

The mel-spectrogram + conv frontend is a STUB per the assignment carve-out:
``input_specs()`` supplies precomputed frame embeddings [B, enc_seq, d].
This module implements the transformer backbone: bidirectional encoder,
causal decoder with self- and cross-attention, learned positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models.layers import (embed_tokens, init_embedding, init_mlp,
                                 init_rmsnorm, mlp, rmsnorm, unembed)
from repro.models.module import ParamBuilder
from repro.models.transformer import DecoderOutput, init_rmsnorm_stacked


def init_encdec(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    b = ParamBuilder(key)
    init_embedding(b, cfg)
    b.add("enc_pos", (cfg.enc_seq, cfg.d_model), (None, "embed"), scale=0.02)
    b.add("dec_pos", (cfg.max_seq_len, cfg.d_model), (None, "embed"),
          scale=0.02)
    enc = b.sub("encoder")
    attn.init_attention(enc, cfg, stacked=cfg.enc_layers)
    init_mlp(enc, cfg, stacked=cfg.enc_layers)
    init_rmsnorm_stacked(enc, "norm1", cfg.d_model, cfg.enc_layers)
    init_rmsnorm_stacked(enc, "norm2", cfg.d_model, cfg.enc_layers)
    dec = b.sub("decoder")
    attn.init_attention(dec, cfg, stacked=cfg.n_layers)
    cross = b.sub("cross")
    attn.init_attention(cross, cfg, stacked=cfg.n_layers)
    init_mlp(dec, cfg, stacked=cfg.n_layers)
    init_rmsnorm_stacked(dec, "norm1", cfg.d_model, cfg.n_layers)
    init_rmsnorm_stacked(dec, "norm_cross", cfg.d_model, cfg.n_layers)
    init_rmsnorm_stacked(dec, "norm2", cfg.d_model, cfg.n_layers)
    init_rmsnorm(b, "enc_final_norm", cfg.d_model)
    init_rmsnorm(b, "final_norm", cfg.d_model)
    return b.build()


def encode(params: dict, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """frames: [B, enc_seq, d] stub frontend embeddings."""
    s = frames.shape[1]
    x = frames + params["enc_pos"][:s].astype(frames.dtype)

    from repro.models.transformer import remat_layer

    @remat_layer
    def body(h, lp):
        h = h + attn.mha_bidirectional(
            lp, rmsnorm(h, lp["norm1"], cfg.norm_eps), cfg)
        h = h + mlp(lp, rmsnorm(h, lp["norm2"], cfg.norm_eps), cfg)
        return h, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return rmsnorm(x, params["enc_final_norm"], cfg.norm_eps)


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            frames: jax.Array, last_only: bool = False) -> DecoderOutput:
    """Teacher-forced training / prefill: tokens [B,S], frames [B,Senc,d]."""
    enc_out = encode(params, cfg, frames)
    b_, s = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    x = x + params["dec_pos"][:s].astype(x.dtype)
    positions = jnp.broadcast_to(jnp.arange(s), (b_, s))

    from repro.models.transformer import remat_layer

    @remat_layer
    def body(h, xs):
        lp, xlp = xs
        h = h + attn.mha_full(lp, rmsnorm(h, lp["norm1"], cfg.norm_eps),
                              cfg, positions)
        h = h + attn.mha_cross(
            xlp, rmsnorm(h, lp["norm_cross"], cfg.norm_eps),
            *attn.cross_kv(xlp, enc_out, cfg), cfg)
        h = h + mlp(lp, rmsnorm(h, lp["norm2"], cfg.norm_eps), cfg)
        return h, None

    x, _ = jax.lax.scan(body, x, (params["decoder"], params["cross"]))
    if last_only:
        x = x[:, -1:]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return DecoderOutput(logits=unembed(params, x, cfg),
                         aux_loss=jnp.zeros((), jnp.float32))


def init_caches(cfg: ModelConfig, batch: int, context: int) -> dict:
    k, v = attn.init_kv_cache(cfg, cfg.n_layers, batch, context)
    kh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": k, "v": v,
        # cross K/V are filled once from the encoder at prefill time
        "cross_k": jnp.zeros((cfg.n_layers, batch, cfg.enc_seq, kh, hd),
                             jnp.bfloat16),
        "cross_v": jnp.zeros((cfg.n_layers, batch, cfg.enc_seq, kh, hd),
                             jnp.bfloat16),
    }


def prefill_cross_kv(params: dict, cfg: ModelConfig, frames: jax.Array,
                     caches: dict) -> dict:
    enc_out = encode(params, cfg, frames)
    dtype = caches["cross_k"].dtype

    def body(_, xlp):
        k, v = attn.cross_kv(xlp, enc_out, cfg)
        return None, (k.astype(dtype), v.astype(dtype))

    _, (ck, cv) = jax.lax.scan(body, None, params["cross"])
    return {**caches, "cross_k": ck, "cross_v": cv}


def decode_step(params: dict, cfg: ModelConfig, token: jax.Array,
                index: jax.Array, caches: dict):
    x = embed_tokens(params, token, cfg)
    x = x + jax.lax.dynamic_slice_in_dim(
        params["dec_pos"], index, 1, axis=0).astype(x.dtype)

    def body(h, xs):
        lp, xlp, ck, cv, xk, xv = xs
        out, k_new, v_new = attn.mha_decode_append(
            lp, rmsnorm(h, lp["norm1"], cfg.norm_eps), cfg, ck, cv, index)
        h = h + out
        h = h + attn.mha_cross(
            xlp, rmsnorm(h, lp["norm_cross"], cfg.norm_eps),
            xk.astype(h.dtype), xv.astype(h.dtype), cfg)
        h = h + mlp(lp, rmsnorm(h, lp["norm2"], cfg.norm_eps), cfg)
        return h, (k_new, v_new)

    # the cross caches are read only; the self-attention stacks take the
    # new position
    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["decoder"], params["cross"], caches["k"],
                  caches["v"], caches["cross_k"], caches["cross_v"]))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, x, cfg), attn.write_positions(
        caches, {"k": k_new, "v": v_new}, index)
