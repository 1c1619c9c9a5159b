"""Plain float32 reference of the Qwen3 dense decoder, and the weights the
benchmark serves it with.

Written from the Qwen3 model card and its ``config.json``
(https://huggingface.co/Qwen/Qwen3-1.7B): pre-norm decoder layers with
RMSNorm; grouped-query attention whose queries and keys are RMS-normalised
per head (qk-norm) before rotary embeddings (rotate-half, base
``rope_theta``), causal softmax scaled by 1/sqrt(head_dim), no biases; a
SwiGLU MLP; a final RMSNorm; the output head tied to the input embedding.

Departures, each deliberate:
- logits are over the first ``vocab_size`` rows of the embedding table: the
  program pads the table to a multiple of 256 rows, and the padding is no
  token;
- the full-sequence forward stands in for decoding through a cache: for
  greedy serving both give the logits of the same positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ref.common import F32, _fp8, linear, rmsnorm


def layout(hp: dict, vocab_rows: int) -> dict:
    """Shapes of the served weights, stacked over layers."""
    L, d, f = hp["num_hidden_layers"], hp["hidden_size"], \
        hp["intermediate_size"]
    h, kh, hd = hp["num_attention_heads"], hp["num_key_value_heads"], \
        hp["head_dim"]
    return {
        "embedding": (vocab_rows, d), "final_norm": (d,),
        "layers": {
            "wq": (L, d, h, hd), "wk": (L, d, kh, hd), "wv": (L, d, kh, hd),
            "wo": (L, h, hd, d), "q_norm": (L, hd), "k_norm": (L, hd),
            "norm1": (L, d), "norm2": (L, d),
            "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
        },
    }


def init_weights(key, hp: dict, vocab_rows: int) -> dict:
    """Random bfloat16 weights: matrices N(0, 1/fan_in), the tied embedding
    N(0, 1/hidden_size) (so the logits have unit spread), norm scales
    1 + N(0, 0.1^2)."""
    shapes = layout(hp, vocab_rows)
    fan_in = {"embedding": hp["hidden_size"], "wq": hp["hidden_size"],
              "wk": hp["hidden_size"], "wv": hp["hidden_size"],
              "wo": hp["num_attention_heads"] * hp["head_dim"],
              "w_gate": hp["hidden_size"], "w_up": hp["hidden_size"],
              "w_down": hp["intermediate_size"]}
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = path[-1].key
        z = jax.random.normal(k, shape, F32)
        if name in fan_in:
            w = z / np.sqrt(fan_in[name])
        else:
            w = 1.0 + 0.1 * z
        out.append(w.astype(jnp.bfloat16))
    return jax.tree_util.tree_unflatten(tree, out)


def _rope(x, theta: float):
    """x [B, T, heads, hd]; rotate-half over the two halves of hd."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(x.shape[1])[:, None] * inv[None, :]       # [T, hd/2]
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make_logits_at(hp: dict, precision: str = "f32"):
    """A jitted ``f(weights, tokens [B, T], positions [B, K]) -> [B, K, V]``:
    the reference's logits at the given positions of each row."""
    L, d = hp["num_hidden_layers"], hp["hidden_size"]
    h, kh, hd = hp["num_attention_heads"], hp["num_key_value_heads"], \
        hp["head_dim"]
    g, eps, V = h // kh, hp["rms_norm_eps"], hp["vocab_size"]
    assert hp["tie_word_embeddings"], "the reference ties the output head"

    def lin(x, w):
        return linear(x, w, precision)

    def layer(x, lp):
        b, t, _ = x.shape
        a = rmsnorm(x, lp["norm1"], eps)
        q = lin(a, lp["wq"].reshape(d, h * hd)).reshape(b, t, h, hd)
        k = lin(a, lp["wk"].reshape(d, kh * hd)).reshape(b, t, kh, hd)
        v = lin(a, lp["wv"].reshape(d, kh * hd)).reshape(b, t, kh, hd)
        q = _rope(rmsnorm(q, lp["q_norm"], eps), hp["rope_theta"])
        k = _rope(rmsnorm(k, lp["k_norm"], eps), hp["rope_theta"])
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        causal = np.tril(np.ones((t, t), bool))
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + lin(o.reshape(b, t, h * hd), lp["wo"].reshape(h * hd, d))
        m = rmsnorm(x, lp["norm2"], eps)
        m = jax.nn.silu(lin(m, lp["w_gate"])) * lin(m, lp["w_up"])
        return x + lin(m, lp["w_down"]), None

    @jax.jit
    def logits_at(w, tokens, positions):
        with jax.default_matmul_precision("highest"):
            table = w["embedding"][:V].astype(F32)
            if precision == "fp8":
                table = _fp8(table, -1)
            x, _ = jax.lax.scan(layer, table[tokens], w["layers"])
            x = jnp.take_along_axis(x, positions[..., None], 1)
            x = rmsnorm(x, w["final_norm"], eps)
            return lin(x, table.T)
    return logits_at
