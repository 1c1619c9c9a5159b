"""Unified model API over all families — the single entry point used by the
training loop, serving engine, dry-run, and benchmarks.

A "batch" is a dict:
    tokens   [B, S] int32           (all families)
    labels   [B, S] int32           (training; -1 = masked)
    frames   [B, enc_seq, d]        (audio stub frontend)
    patches  [B, vision_tokens, d]  (VLM stub frontend)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, hybrid, transformer
from repro.models.layers import cross_entropy_loss
from repro.models.transformer import DecoderOutput


def init_params(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    """Returns (params, logical-axis specs)."""
    if cfg.family == "audio":
        return encdec.init_encdec(key, cfg)
    if cfg.family == "hybrid":
        return hybrid.init_hybrid(key, cfg)
    return transformer.init_decoder(key, cfg)


def abstract_params(cfg: ModelConfig) -> tuple[dict, dict]:
    """(ShapeDtypeStruct tree, logical-axis spec tree) of
    :func:`init_params`, without allocating anything."""
    holder = {}

    def build(key):
        params, holder["specs"] = init_params(key, cfg)
        return params

    shapes = jax.eval_shape(build, jax.random.PRNGKey(0))
    return shapes, holder["specs"]


@functools.lru_cache(maxsize=None)
def _init_program(cfg: ModelConfig):
    def init_params_program(key):
        return init_params(key, cfg)[0]
    return jax.jit(init_params_program)


def init_params_compiled(key: jax.Array, cfg: ModelConfig
                         ) -> tuple[dict, dict]:
    """:func:`init_params` as one compiled program, on the default device.

    Eager init dispatches, and on an accelerator compiles, every op of
    every parameter: minutes for a full-width model on a TPU.  One program
    per config compiles once."""
    return _init_program(cfg)(key), abstract_params(cfg)[1]


def forward(params: dict, cfg: ModelConfig, batch: dict) -> DecoderOutput:
    if cfg.family == "audio":
        return encdec.forward(params, cfg, batch["tokens"], batch["frames"])
    if cfg.family == "hybrid":
        return hybrid.forward(params, cfg, batch["tokens"])
    extra = batch.get("patches")
    return transformer.forward(params, cfg, batch["tokens"],
                               extra_embeddings=extra)


def loss_fn(params: dict, cfg: ModelConfig, batch: dict,
            aux_weight: float = 0.01) -> tuple[jax.Array, DecoderOutput]:
    out = forward(params, cfg, batch)
    ce = cross_entropy_loss(out.logits, batch["labels"], cfg.vocab)
    return ce + aux_weight * out.aux_loss, out


def init_caches(cfg: ModelConfig, batch: int, context: int) -> dict:
    if cfg.family == "audio":
        return encdec.init_caches(cfg, batch, context)
    if cfg.family == "hybrid":
        return hybrid.init_caches(cfg, batch, context)
    return transformer.init_caches(cfg, batch, context)


def prefill_encoder(params: dict, cfg: ModelConfig, batch: dict,
                    caches: dict) -> dict:
    """Enc-dec models: run the encoder once and stash cross-K/V."""
    if cfg.family == "audio":
        return encdec.prefill_cross_kv(params, cfg, batch["frames"], caches)
    return caches


def decode_step(params: dict, cfg: ModelConfig, token: jax.Array,
                index: jax.Array, caches: dict) -> tuple[jax.Array, dict]:
    if cfg.family == "audio":
        return encdec.decode_step(params, cfg, token, index, caches)
    if cfg.family == "hybrid":
        return hybrid.decode_step(params, cfg, token, index, caches)
    return transformer.decode_step(params, cfg, token, index, caches)


def supports_long_context(cfg: ModelConfig) -> bool:
    return cfg.has_subquadratic_attention


def make_dummy_batch(cfg: ModelConfig, batch: int, seq: int,
                     key: jax.Array | None = None) -> dict:
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    out = {
        "tokens": jax.random.randint(k1, (batch, seq), 0, cfg.vocab,
                                     jnp.int32),
        "labels": jax.random.randint(k2, (batch, seq), 0, cfg.vocab,
                                     jnp.int32),
    }
    if cfg.family == "audio":
        out["frames"] = jax.random.normal(
            k1, (batch, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm" and cfg.vision_tokens:
        out["patches"] = jax.random.normal(
            k2, (batch, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
    return out


# -- logical-axis spec trees (consumed by the dry-run sharding builder) --------

KV_SPEC = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
SSM_CONV_SPEC = ("layers", "batch", None, "ssm_inner")
SSM_STATE_SPEC = ("layers", "batch", "ssm_inner", None, None)


WKV_LOCAL_SPEC = ("layers", "layers2", "batch", "cache_seq", "kv_heads",
                  "head_dim")
WKV_TAIL_SPEC = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")


def cache_specs(cfg: ModelConfig) -> dict:
    """Logical axes mirroring :func:`init_caches`' structure."""
    if cfg.family == "ssm":
        return {"ssm": {"conv": SSM_CONV_SPEC, "state": SSM_STATE_SPEC}}
    if cfg.kv_quant and cfg.family in ("dense", "vlm") \
            and not cfg.n_experts:
        return {"k_q": KV_SPEC, "k_s": KV_SPEC,
                "v_q": KV_SPEC, "v_s": KV_SPEC}
    if (cfg.windowed_cache and cfg.sliding_window and cfg.global_every
            and not cfg.n_experts and cfg.family not in ("audio", "hybrid")):
        from repro.models.transformer import windowed_layout
        _, _, tail = windowed_layout(cfg)
        out = {"local_k": WKV_LOCAL_SPEC, "local_v": WKV_LOCAL_SPEC,
               "global_k": KV_SPEC, "global_v": KV_SPEC}
        if tail:
            out["tail_k"] = WKV_TAIL_SPEC
            out["tail_v"] = WKV_TAIL_SPEC
        return out
    if cfg.family == "hybrid":
        from repro.models.hybrid import _group_shape
        _, remainder = _group_shape(cfg)
        out = {
            "ssm": {"conv": SSM_CONV_SPEC, "state": SSM_STATE_SPEC},
            "attn_k": KV_SPEC, "attn_v": KV_SPEC,
        }
        if remainder:
            out["ssm_tail"] = {"conv": SSM_CONV_SPEC,
                               "state": SSM_STATE_SPEC}
        return out
    if cfg.family == "audio":
        return {"k": KV_SPEC, "v": KV_SPEC,
                "cross_k": KV_SPEC, "cross_v": KV_SPEC}
    return {"k": KV_SPEC, "v": KV_SPEC}


def batch_specs(cfg: ModelConfig, with_labels: bool) -> dict:
    out = {"tokens": ("batch", "seq")}
    if with_labels:
        out["labels"] = ("batch", "seq")
    if cfg.family == "audio":
        out["frames"] = ("batch", None, None)
    if cfg.family == "vlm" and cfg.vision_tokens:
        out["patches"] = ("batch", None, None)
    return out


def prefill(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Forward over the prompt returning ONLY the last position's logits —
    full-sequence logits at 32k x 262k vocab would be terabytes."""
    if cfg.family == "audio":
        from repro.models import encdec
        return encdec.forward(params, cfg, batch["tokens"], batch["frames"],
                              last_only=True).logits
    if cfg.family == "hybrid":
        from repro.models import hybrid
        return hybrid.forward(params, cfg, batch["tokens"],
                              last_only=True).logits
    return transformer.forward(params, cfg, batch["tokens"],
                               extra_embeddings=batch.get("patches"),
                               last_only=True).logits
