"""Shared neural layers: RMSNorm, RoPE, embeddings, gated MLPs."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.module import ParamBuilder
from repro.sharding.partitioning import constrain

VOCAB_PAD_MULTIPLE = 256


def padded_vocab(cfg: ModelConfig) -> int:
    v = cfg.vocab
    m = VOCAB_PAD_MULTIPLE
    return (v + m - 1) // m * m


# -- RMSNorm -------------------------------------------------------------------

def init_rmsnorm(b: ParamBuilder, name: str, dim: int) -> None:
    b.add(name, (dim,), ("norm",), init="ones")


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


# -- RoPE ----------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)            # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..,S,hd/2]
    angles = angles[..., None, :]                        # [.., S, 1, hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- Embedding / unembedding ------------------------------------------------------

def init_embedding(b: ParamBuilder, cfg: ModelConfig) -> None:
    pv = padded_vocab(cfg)
    b.add("embedding", (pv, cfg.d_model), ("vocab", "embed"),
          scale=1.0)
    if not cfg.tie_embeddings:
        b.add("unembed", (cfg.d_model, pv), ("embed", "vocab"))


def embed_tokens(params: dict, tokens: jax.Array, cfg: ModelConfig
                 ) -> jax.Array:
    table = params["embedding"]
    if cfg.embed_impl == "onehot":
        # scatter/gather-free lookup: partitions along the sharded vocab
        # axis with one [B,S,d] psum; backward is an einsum (no scatter-add
        # that would force XLA to all-gather the table / activations)
        pv = table.shape[0]
        vocab_ids = jax.lax.broadcasted_iota(jnp.int32, (pv,), 0)
        onehot = (tokens[..., None] == vocab_ids).astype(table.dtype)
        x = jnp.einsum("bsv,vd->bsd", onehot, table)
    else:
        x = jnp.take(table, tokens, axis=0)
    if cfg.family in ("dense", "vlm"):  # gemma-style sqrt(d) scaling is safe
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return constrain(x, ("batch", "seq", None))


def unembed(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    table = (params["embedding"].T if cfg.tie_embeddings
             else params["unembed"])
    logits = jnp.einsum("bsd,dv->bsv", x, table.astype(x.dtype))
    logits = constrain(logits, ("batch", "seq", "vocab"))
    if cfg.vocab_size is not None and cfg.vocab_size < cfg.vocab:
        # rows past the published vocabulary pad the table and are no
        # token: they never win an argmax.  Iota-compare masking keeps the
        # op elementwise along the sharded vocab axis
        ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        logits = jnp.where(ids >= cfg.vocab_size,
                           jnp.finfo(logits.dtype).min, logits)
    return logits


# -- Gated MLP ---------------------------------------------------------------------

def init_mlp(b: ParamBuilder, cfg: ModelConfig, d_ff: int | None = None,
             stacked: int | None = None) -> None:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    lead = (stacked,) if stacked else ()
    lax = ("layers",) if stacked else ()
    b.add("w_gate", lead + (d, f), lax + ("embed", "ffn"))
    b.add("w_up", lead + (d, f), lax + ("embed", "ffn"))
    b.add("w_down", lead + (f, d), lax + ("ffn", "embed"))


def mlp(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    gate = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
    up = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    gate = constrain(gate, ("batch", "seq", "ffn"))
    if cfg.act == "geglu":
        act = jax.nn.gelu(gate.astype(jnp.float32)).astype(x.dtype)
    elif cfg.act == "swiglu":
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)
    else:
        act = jax.nn.gelu(gate.astype(jnp.float32)).astype(x.dtype)
    h = act * up
    out = jnp.einsum("bsf,fd->bsd", h, params["w_down"])
    return constrain(out, ("batch", "seq", None))


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       vocab: int) -> jax.Array:
    """Mean CE over valid (label >= 0) positions; padded vocab masked out.

    Deliberately scatter/gather-free: an ``.at[..., vocab:].set()`` or
    ``take_along_axis`` on the vocab axis defeats SPMD partitioning — XLA
    all-gathers the full [B,S,V] f32 logits (5GB x fwd/bwd/remat x
    microbatches measured on qwen3 train — EXPERIMENTS.md §Perf iter 2).
    Iota-compare masking and a one-hot contraction keep every op
    elementwise or a reduction along the sharded vocab axis.
    """
    logits = logits.astype(jnp.float32)
    pv = logits.shape[-1]
    vocab_ids = jax.lax.broadcasted_iota(jnp.int32, (pv,), 0)
    if pv > vocab:
        logits = logits + jnp.where(vocab_ids >= vocab, -1e9, 0.0)
    valid = labels >= 0
    safe_labels = jnp.maximum(labels, 0)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot = (safe_labels[..., None] == vocab_ids).astype(logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = (logz - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)
