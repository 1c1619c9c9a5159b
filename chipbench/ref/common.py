"""Pieces shared by the plain references: float32 norms, the linear map in
the reference's precision or in the control's, and the widest-gap reading.

Nothing here imports the program.  The references read weights laid out as
the served program keeps them (stacked over layers, bfloat16) and compute in
float32 under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = float(jnp.finfo(FP8).max)


def rmsnorm(x, w, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the reduced axis), as an fp8 deployment stores it."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(F32) * scale


def linear(x, w, precision: str):
    """``x [..., k] @ w [k, n]`` in float32.  The control ("fp8") rounds the
    activations per row and the weights per output column to float8 e4m3
    first: the precision below the configuration's bfloat16."""
    x, w = x.astype(F32), w.astype(F32)
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return x @ w


def widest_gap(logits: np.ndarray, tokens: np.ndarray) -> float:
    """Largest amount by which a chosen token's logit lies below the best
    logit at its position.  logits [N, V] from the reference, tokens [N].
    A token outside ``[0, V)`` is no token of the vocabulary: its gap is
    infinite."""
    logits = np.asarray(logits, np.float64)
    tokens = np.asarray(tokens)
    if ((tokens < 0) | (tokens >= logits.shape[1])).any():
        return float("inf")
    chosen = np.take_along_axis(logits, tokens[:, None], 1)[:, 0]
    return float((logits.max(1) - chosen).max())
