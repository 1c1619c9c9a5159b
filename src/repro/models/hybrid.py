"""Zamba2-style hybrid: Mamba2 backbone + a single weight-TIED attention
block applied every ``attn_every`` Mamba2 layers (arXiv:2411.15242).

The shared block's params exist once; the scan over groups closes over them
(this is zamba2's actual design — the attention block weights are shared
across all its applications, which is why an 81-layer 7B model stays 7B).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import ssm as ssm_lib
from repro.models.layers import (embed_tokens, init_embedding, init_mlp,
                                 init_rmsnorm, mlp, rmsnorm, unembed)
from repro.models.module import ParamBuilder
from repro.models.transformer import DecoderOutput, init_rmsnorm_stacked


def _group_shape(cfg: ModelConfig) -> tuple[int, int]:
    m = max(cfg.attn_every, 1)
    assert cfg.n_layers % m == 0 or cfg.n_layers > m, \
        "hybrid stack needs at least one full group"
    n_groups = cfg.n_layers // m
    remainder = cfg.n_layers - n_groups * m
    return n_groups, remainder


def init_hybrid(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    b = ParamBuilder(key)
    init_embedding(b, cfg)
    n_groups, remainder = _group_shape(cfg)
    m = max(cfg.attn_every, 1)
    grp = b.sub("mamba_layers")          # [n_groups*m] stacked
    ssm_lib.init_ssm(grp, cfg, stacked=n_groups * m)
    init_rmsnorm_stacked(grp, "norm1", cfg.d_model, n_groups * m)
    if remainder:
        tail = b.sub("mamba_tail")
        ssm_lib.init_ssm(tail, cfg, stacked=remainder)
        init_rmsnorm_stacked(tail, "norm1", cfg.d_model, remainder)
    shared = b.sub("shared_attn")        # weight-tied block
    attn.init_attention(shared, cfg)
    init_mlp(shared, cfg)
    init_rmsnorm(shared, "norm1", cfg.d_model)
    init_rmsnorm(shared, "norm2", cfg.d_model)
    init_rmsnorm(b, "final_norm", cfg.d_model)
    return b.build()


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            extra_embeddings=None, last_only: bool = False) -> DecoderOutput:
    b_, s = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(s), (b_, s))
    n_groups, remainder = _group_shape(cfg)
    m = max(cfg.attn_every, 1)
    shared = params["shared_attn"]

    from repro.models.transformer import remat_layer

    @remat_layer
    def mamba_block(h, lp):
        return h + ssm_lib.ssm_forward(
            lp, rmsnorm(h, lp["norm1"], cfg.norm_eps), cfg)[0], None

    @remat_layer
    def group_body(h, lp_group):
        h, _ = jax.lax.scan(mamba_block, h, lp_group)
        # shared attention + MLP block (weight-tied across groups)
        h = h + attn.mha_full(shared,
                              rmsnorm(h, shared["norm1"], cfg.norm_eps),
                              cfg, positions)
        h = h + mlp(shared, rmsnorm(h, shared["norm2"], cfg.norm_eps), cfg)
        return h, None

    grouped = jax.tree_util.tree_map(
        lambda a: a.reshape((n_groups, m) + a.shape[1:]),
        params["mamba_layers"])
    x, _ = jax.lax.scan(group_body, x, grouped)
    if remainder:
        x, _ = jax.lax.scan(mamba_block, x, params["mamba_tail"])
    if last_only:
        x = x[:, -1:]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return DecoderOutput(logits=unembed(params, x, cfg),
                         aux_loss=jnp.zeros((), jnp.float32))


def init_caches(cfg: ModelConfig, batch: int, context: int) -> dict:
    n_groups, remainder = _group_shape(cfg)
    m = max(cfg.attn_every, 1)
    k, v = attn.init_kv_cache(cfg, n_groups, batch, context)
    caches = {
        "ssm": ssm_lib.init_ssm_cache(cfg, n_groups * m, batch),
        "attn_k": k, "attn_v": v,
    }
    if remainder:
        caches["ssm_tail"] = ssm_lib.init_ssm_cache(cfg, remainder, batch)
    return caches


def decode_step(params: dict, cfg: ModelConfig, token: jax.Array,
                index: jax.Array, caches: dict):
    x = embed_tokens(params, token, cfg)
    n_groups, remainder = _group_shape(cfg)
    m = max(cfg.attn_every, 1)
    shared = params["shared_attn"]

    def mamba_step(carry, xs):
        h, ssm_c = carry
        lp, layer = xs
        out, ssm_c = ssm_lib.ssm_decode_layer(
            lp, rmsnorm(h, lp["norm1"], cfg.norm_eps), ssm_c, layer, cfg)
        return (h + out, ssm_c), None

    grouped = jax.tree_util.tree_map(
        lambda a: a.reshape((n_groups, m) + a.shape[1:]),
        params["mamba_layers"])
    # group g's layer j is g * m + j of the flat [n_groups * m] ssm stacks
    layers = jnp.arange(n_groups * m).reshape(n_groups, m)

    def group_body(carry, xs):
        lp_group, layer_group, ck, cv = xs
        (h, ssm_c), _ = jax.lax.scan(mamba_step, carry,
                                     (lp_group, layer_group))
        out, k_new, v_new = attn.mha_decode_append(
            shared, rmsnorm(h, shared["norm1"], cfg.norm_eps), cfg, ck, cv,
            index)
        h = h + out
        h = h + mlp(shared, rmsnorm(h, shared["norm2"], cfg.norm_eps), cfg)
        return (h, ssm_c), (k_new, v_new)

    (x, ssm_c), (k_new, v_new) = jax.lax.scan(
        group_body, (x, caches["ssm"]),
        (grouped, layers, caches["attn_k"], caches["attn_v"]))
    new = attn.write_positions(caches, {"attn_k": k_new, "attn_v": v_new},
                               index)
    new["ssm"] = ssm_c
    if remainder:
        (x, new["ssm_tail"]), _ = jax.lax.scan(
            mamba_step, (x, caches["ssm_tail"]),
            (params["mamba_tail"], jnp.arange(remainder)))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, x, cfg), new
