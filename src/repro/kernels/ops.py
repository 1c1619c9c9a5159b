"""jit'd public wrappers around the Pallas kernels.

These adapt the model-layer layouts ([B,S,H,D]) to the kernel layouts
([B,H,S,D]), pad ragged sequence lengths to block multiples, and decide in
one place whether a kernel is interpreted (:func:`resolve_interpret`) — the
model code calls these, never ``pallas_call`` directly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


def resolve_interpret(interpret: bool | None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    On a TPU backend a kernel is always compiled for the chip, whatever the
    caller asked.  Elsewhere the default (``None``) interprets it; an
    explicit ``False`` compiles it, which is how a kernel is compiled for a
    described chip that is not attached.
    """
    if jax.default_backend() == "tpu":
        return False
    return True if interpret is None else interpret


def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int | None = None,
              block_q: int = 128, block_k: int = 128,
              interpret: bool | None = None) -> jax.Array:
    """Model-layout flash attention.

    q: [B,S,H,hd]; k/v: [B,S,KH,hd] -> [B,S,H,hd].
    Pads S up to a block multiple; padded kv positions are masked out by
    causality (they sit in the future) and padded q rows are sliced off.
    """
    b, s, h, hd = q.shape
    kh = k.shape[2]
    blk = max(block_q, block_k)
    pad = (-s) % blk
    if pad:
        zq = jnp.zeros((b, pad, h, hd), q.dtype)
        zk = jnp.zeros((b, pad, kh, hd), k.dtype)
        q = jnp.concatenate([q, zq], axis=1)
        k = jnp.concatenate([k, zk], axis=1)
        v = jnp.concatenate([v, zk], axis=1)
    out = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=causal,
                          window=window, block_q=min(block_q, q.shape[1]),
                          block_k=min(block_k, q.shape[1]),
                          interpret=resolve_interpret(interpret))
    out = out.transpose(0, 2, 1, 3)
    return out[:, :s] if pad else out


def ssd_mixer(x: jax.Array, dt: jax.Array, a: jax.Array, b_in: jax.Array,
              c_in: jax.Array, *, chunk: int = 128,
              interpret: bool | None = None) -> jax.Array:
    """Model-layout SSD: x [B,S,H,P], dt [B,S,H], a [H], b/c [B,S,N].

    Pads S to a chunk multiple with dt=0 (zero dt => exp(0)=1 decay and no
    state injection, so padding is exact).
    """
    b, s, h, p = x.shape
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0)))
    y = ssd_scan(x, dt, a, b_in, c_in, chunk=min(chunk, x.shape[1]),
                 interpret=resolve_interpret(interpret))
    return y[:, :s] if pad else y
