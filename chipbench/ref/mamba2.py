"""Plain float32 reference of Mamba-2, and the weights the benchmark serves
it with.

Written from "Transformers are SSMs" (arXiv:2405.21060, sections 6-7) and
the ``state-spaces/mamba2-2.7b`` configuration: each layer is
``x + mixer(rmsnorm(x))``.  The mixer projects to (z, xBC, dt); xBC passes a
causal depthwise convolution and SiLU and splits into x (heads of
``headdim``), B and C (one group of ``d_state``); dt = softplus(dt +
dt_bias), A = -exp(A_log); the sequence map is the paper's matrix form

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t

per head; then y * silu(z) is RMS-normalised (the gated norm, applied after
the gate) and projected out.  A final RMSNorm and the output head tied to
the embedding give the logits.

Departures, each deliberate:
- logits are over the first ``vocab_size`` rows of the embedding table:
  the rows past it pad the table and are no token;
- the quadratic matrix form stands in for the recurrence the program runs:
  the paper shows they compute the same map (its "state space duality").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ref.common import F32, _fp8, linear, rmsnorm


def _dims(hp: dict):
    d_inner = hp["expand"] * hp["d_model"]
    n, p = hp["d_state"], hp["headdim"]
    return d_inner, d_inner // p, p, n


def layout(hp: dict, vocab_rows: int) -> dict:
    """Shapes of the served weights, stacked over layers."""
    L, d, w = hp["n_layer"], hp["d_model"], hp["d_conv"]
    d_inner, h, _, n = _dims(hp)
    ch = d_inner + 2 * n
    return {
        "embedding": (vocab_rows, d), "final_norm": (d,),
        "layers": {
            "in_proj": (L, d, 2 * d_inner + 2 * n + h), "conv_w": (L, w, ch),
            "conv_b": (L, ch), "A_log": (L, h), "D": (L, h),
            "dt_bias": (L, h), "norm": (L, d_inner),
            "out_proj": (L, d_inner, d), "norm1": (L, d),
        },
    }


def init_weights(key, hp: dict, vocab_rows: int) -> dict:
    """Random bfloat16 weights after the paper's initialisation: A in
    [1, 16], dt in [1e-3, 1e-1] through the inverse softplus of dt_bias,
    projections N(0, 1/fan_in), convolution U(-1/sqrt(w), 1/sqrt(w)),
    norm scales 1 + N(0, 0.1^2)."""
    shapes = layout(hp, vocab_rows)
    d_inner = _dims(hp)[0]
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    bound = 1.0 / np.sqrt(hp["d_conv"])
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = path[-1].key
        z = jax.random.normal(k, shape, F32)
        u = jax.random.uniform(k, shape, F32)
        w = {
            "embedding": lambda: z / np.sqrt(hp["d_model"]),
            "in_proj": lambda: z / np.sqrt(hp["d_model"]),
            "out_proj": lambda: z / np.sqrt(d_inner),
            "conv_w": lambda: (2 * u - 1) * bound,
            "conv_b": lambda: (2 * u - 1) * bound,
            "A_log": lambda: jnp.log(1 + 15 * u),
            "dt_bias": lambda: _inv_softplus(
                jnp.exp(np.log(1e-3) + u * np.log(1e2))),
            "D": lambda: 1.0 + 0.1 * z,
        }.get(name, lambda: 1.0 + 0.1 * z)()
        out.append(w.astype(jnp.bfloat16))
    return jax.tree_util.tree_unflatten(tree, out)


def _inv_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def make_logits_at(hp: dict, precision: str = "f32"):
    """A jitted ``f(weights, tokens [B, T], positions [B, K]) -> [B, K, V]``:
    the reference's logits at the given positions of each row."""
    d_inner, h, p, n = _dims(hp)
    eps, V, W = hp["norm_epsilon"], hp["vocab_size"], hp["d_conv"]
    assert hp["tie_embeddings"], "the reference ties the output head"

    def lin(x, w):
        return linear(x, w, precision)

    def mixer(u, lp):
        b, t, _ = u.shape
        zxbcdt = lin(u, lp["in_proj"])
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * n]
        dt = zxbcdt[..., 2 * d_inner + 2 * n:]
        cw = lp["conv_w"].astype(F32)                       # [W, ch]
        pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
        xbc = sum(pad[:, i:i + t] * cw[i] for i in range(W))
        xbc = jax.nn.silu(xbc + lp["conv_b"].astype(F32))
        x = xbc[..., :d_inner].reshape(b, t, h, p)
        B, C = xbc[..., d_inner:d_inner + n], xbc[..., d_inner + n:]
        dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))   # [b, t, h]
        A = -jnp.exp(lp["A_log"].astype(F32))                  # [h]
        cum = jnp.cumsum(dt * A, axis=1)                       # [b, t, h]
        seg = cum[:, :, None, :] - cum[:, None, :, :]          # [b, t, s, h]
        causal = np.tril(np.ones((t, t), bool))[None, :, :, None]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        m = jnp.einsum("btn,bsn->bts", C, B)[..., None] * decay
        y = jnp.einsum("btsh,bsh,bshp->bthp", m, dt, x)
        y = y + lp["D"].astype(F32)[:, None] * x
        y = y.reshape(b, t, d_inner) * jax.nn.silu(z)
        return lin(rmsnorm(y, lp["norm"], eps), lp["out_proj"])

    def layer(x, lp):
        return x + mixer(rmsnorm(x, lp["norm1"], eps), lp), None

    @jax.jit
    def logits_at(w, tokens, positions):
        with jax.default_matmul_precision("highest"):
            table = w["embedding"][:V].astype(F32)
            if precision == "fp8":
                table = _fp8(table, -1)
            x, _ = jax.lax.scan(layer, table[tokens], w["layers"])
            x = jnp.take_along_axis(x, positions[..., None], 1)
            return lin(rmsnorm(x, w["final_norm"], eps), table.T)
    return logits_at
