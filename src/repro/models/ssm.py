"""Mamba2 (state-space duality) mixer — chunked SSD prefill + recurrent
decode (arXiv:2405.21060), pure JAX with a Pallas fast path for the
chunk-local quadratic form (repro.kernels.ssd_scan).

Shapes: d_inner = expand * d_model, H heads of dim P = d_inner/H, state N.
The SSD computation per chunk of length Q:

    dA      = a * dt                          (a = -exp(A_log) < 0)
    L[j,i]  = exp(csum[j] - csum[i])  (i<=j)  intra-chunk decay
    Y_intra = ((C Bᵀ) ⊙ L) @ (dt ⊙ x)
    S_chunk = Σ_i exp(csum[Q]-csum[i]) dt_i B_i ⊗ x_i
    Y_inter = exp(csum[j]) C_j · S_prev
    S_next  = exp(csum[Q]) S_prev + S_chunk

scanned over chunks with lax.scan — sequential in chunk count, parallel in
batch/heads, which maps naturally onto the TPU (the recurrence is tiny
[B,H,P,N] state, everything else is MXU matmuls).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import rmsnorm
from repro.models.module import ParamBuilder
from repro.sharding.partitioning import constrain


def ssm_dims(cfg: ModelConfig) -> tuple[int, int, int, int]:
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = cfg.ssm_heads or max(1, d_inner // 64)
    p = d_inner // nheads
    return d_inner, nheads, p, cfg.ssm_state


def init_ssm(b: ParamBuilder, cfg: ModelConfig,
             stacked: int | None = None) -> None:
    d = cfg.d_model
    d_inner, h, p, n = ssm_dims(cfg)
    conv_ch = d_inner + 2 * n
    lead = (stacked,) if stacked else ()
    lx = ("layers",) if stacked else ()
    b.add("in_proj", lead + (d, 2 * d_inner + 2 * n + h),
          lx + ("embed", "ssm_inner"))
    b.add("conv_w", lead + (cfg.conv_width, conv_ch), lx + ("conv", "ssm_inner"))
    b.add("conv_b", lead + (conv_ch,), lx + ("ssm_inner",), init="zeros")
    b.add("A_log", lead + (h,), lx + ("norm",), init="zeros")
    b.add("D", lead + (h,), lx + ("norm",), init="ones")
    b.add("dt_bias", lead + (h,), lx + ("norm",), init="zeros")
    b.add("norm", lead + (d_inner,), lx + ("ssm_inner",), init="ones")
    b.add("out_proj", lead + (d_inner, d), lx + ("ssm_inner", "embed"))


def _split_proj(params, x, cfg):
    d_inner, h, p, n = ssm_dims(cfg)
    proj = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xbc, dt = jnp.split(proj, [d_inner, 2 * d_inner + 2 * n], axis=-1)
    return z, xbc, dt


def _causal_conv(xbc, params, cfg):
    w = params["conv_w"]                                  # [W, ch]
    width = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i] for i in range(width))
    return jax.nn.silu((out + params["conv_b"]).astype(jnp.float32)
                       ).astype(xbc.dtype)


def ssd_chunked(x, dt, a, B_in, C_in, chunk: int, state0=None,
                use_kernel: bool = False):
    """Core SSD over a full sequence.

    x: [B,S,H,P]; dt: [B,S,H] (post-softplus); a: [H] (negative);
    B_in/C_in: [B,S,N].  Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    b_, s, h, p = x.shape
    n = B_in.shape[-1]
    q = min(chunk, s)
    while s % q != 0:
        q //= 2
    nc = s // q

    xc = x.reshape(b_, nc, q, h, p)
    dtc = dt.reshape(b_, nc, q, h).astype(jnp.float32)
    bc = B_in.reshape(b_, nc, q, n)
    cc = C_in.reshape(b_, nc, q, n)
    a = a.astype(jnp.float32)

    if state0 is None:
        state0 = jnp.zeros((b_, h, p, n), jnp.float32)

    @jax.checkpoint
    def step(state, xs):
        xq, dtq, bq, cq = xs          # [B,q,H,P], [B,q,H], [B,q,N], [B,q,N]
        da = dtq * a                  # [B,q,H]
        csum = jnp.cumsum(da, axis=1)                     # [B,q,H]
        total = csum[:, -1:, :]                           # [B,1,H]
        # intra-chunk: scores[j,i] = C_j.B_i * exp(csum_j - csum_i), i<=j
        seg = csum[:, :, None, :] - csum[:, None, :, :]   # [B,q,q,H]
        causal = jnp.tril(jnp.ones((q, q), jnp.bool_))
        l_mat = jnp.where(causal[None, :, :, None], jnp.exp(seg), 0.0)
        cb = jnp.einsum("bjn,bin->bji", cq.astype(jnp.float32),
                        bq.astype(jnp.float32))           # [B,q,q]
        scores = cb[:, :, :, None] * l_mat                # [B,q(j),q(i),H]
        dx = dtq[..., None] * xq.astype(jnp.float32)      # [B,q,H,P]
        y_intra = jnp.einsum("bjih,bihp->bjhp", scores, dx)
        # inter-chunk: contribution of the carried state
        y_inter = jnp.einsum("bjn,bhpn->bjhp", cq.astype(jnp.float32),
                             state) * jnp.exp(csum)[..., None]
        # state update
        decay_to_end = jnp.exp(total - csum)              # [B,q,H]
        s_chunk = jnp.einsum("bihp,bin,bih->bhpn", dx,
                             bq.astype(jnp.float32), decay_to_end)
        state = jnp.exp(total)[:, 0, :, None, None] * state + s_chunk
        return state, (y_intra + y_inter).astype(x.dtype)

    xs = (xc.transpose(1, 0, 2, 3, 4), dtc.transpose(1, 0, 2, 3),
          bc.transpose(1, 0, 2, 3), cc.transpose(1, 0, 2, 3))
    final, ys = jax.lax.scan(step, state0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b_, s, h, p)
    return y, final


def ssm_forward(params: dict, x: jax.Array, cfg: ModelConfig
                ) -> tuple[jax.Array, jax.Array, jax.Array | None]:
    """Full-sequence Mamba2 mixer (training / prefill).  x:[B,S,d].

    Returns (y [B,S,d]; the convolution's input [B,S,ch], whose last rows
    :func:`conv_cache` keeps for decoding; the SSD's final state
    [B,H,P,N] float32, None on the Pallas path).  The convolution and the
    ``D`` skip run in float32 and round after, as :func:`ssm_decode_step`
    runs them, so that a prefill leaves what decoding the prompt token by
    token leaves."""
    d_inner, h, p, n = ssm_dims(cfg)
    b_, s, _ = x.shape
    f32 = jnp.float32
    z, xbc_in, dt = _split_proj(params, x, cfg)
    xbc = _causal_conv(xbc_in.astype(f32), params, cfg).astype(x.dtype)
    x_ssm, b_ssm, c_ssm = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    x_heads = x_ssm.reshape(b_, s, h, p)
    x_heads = constrain(x_heads, ("batch", "seq", "ssm_inner", None))
    dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"].astype(f32))
    a = -jnp.exp(params["A_log"].astype(f32))
    if cfg.ssm_impl == "pallas":
        from repro.kernels.ops import ssd_mixer
        y = ssd_mixer(x_heads, dt, a, b_ssm.astype(f32), c_ssm.astype(f32),
                      chunk=cfg.ssm_chunk)
        state = None
    else:
        y, state = ssd_chunked(x_heads.astype(f32), dt, a, b_ssm, c_ssm,
                               cfg.ssm_chunk)
    y = y.astype(f32) + params["D"].astype(f32)[None, None, :, None] \
        * x_heads.astype(f32)
    y = y.reshape(b_, s, d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(f32)).astype(y.dtype)
    y = rmsnorm(y, params["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    return constrain(out, ("batch", "seq", None)), xbc_in, state


def conv_cache(xbc: jax.Array, cfg: ModelConfig, dtype) -> jax.Array:
    """The decode step's conv cache [B,W-1,ch] after the convolution's
    input rows ``xbc`` [B,S,ch]: the last ``conv_width - 1`` of them, zeros
    before the first, in ``dtype``: what the decode steps' window leaves."""
    w = cfg.conv_width - 1
    return jnp.pad(xbc, ((0, 0), (w, 0), (0, 0)))[:, -w:].astype(dtype)


# -- recurrent decode ----------------------------------------------------------

def init_ssm_cache(cfg: ModelConfig, n_layers: int, batch: int,
                   dtype=jnp.float32):
    d_inner, h, p, n = ssm_dims(cfg)
    conv_ch = d_inner + 2 * n
    return {
        "conv": jnp.zeros((n_layers, batch, cfg.conv_width - 1, conv_ch),
                          dtype),
        "state": jnp.zeros((n_layers, batch, h, p, n), dtype),
    }


def ssm_decode_step(params: dict, x: jax.Array, cache_conv, cache_state,
                    cfg: ModelConfig):
    """One-token step. x:[B,1,d]; cache_conv:[B,W-1,ch];
    cache_state:[B,H,P,N].  Returns (y, cache_conv, cache_state)."""
    d_inner, h, p, n = ssm_dims(cfg)
    b_ = x.shape[0]
    z, xbc, dt = _split_proj(params, x, cfg)
    xbc = xbc[:, 0]                                     # [B, ch]
    # conv over the cached window
    w = params["conv_w"]
    window = jnp.concatenate([cache_conv, xbc[:, None, :]], axis=1)
    conv = (window * w[None]).sum(axis=1) + params["conv_b"]
    conv = jax.nn.silu(conv.astype(jnp.float32)).astype(x.dtype)
    cache_conv = window[:, 1:, :]
    x_ssm, b_ssm, c_ssm = jnp.split(conv, [d_inner, d_inner + n], axis=-1)
    xh = x_ssm.reshape(b_, h, p).astype(jnp.float32)
    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                          + params["dt_bias"].astype(jnp.float32))  # [B,H]
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    # the ops that read and write the recurrent state carry this scope in
    # their HLO metadata, for the device trace (chipbench's ssm.state_ms)
    with jax.named_scope("repro.ssm.state"):
        decay = jnp.exp(dt1 * a)                        # [B,H]
        outer = jnp.einsum("bhp,bn->bhpn", dt1[..., None] * xh,
                           b_ssm.astype(jnp.float32))
        state = cache_state * decay[..., None, None] + outer
        y = jnp.einsum("bhpn,bn->bhp", state, c_ssm.astype(jnp.float32))
        y = y + params["D"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(b_, 1, d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    y = rmsnorm(y, params["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    return constrain(out, ("batch", "seq", None)), cache_conv, state


def ssm_decode_layer(params: dict, x: jax.Array, caches: dict,
                     layer: jax.Array, cfg: ModelConfig
                     ) -> tuple[jax.Array, dict]:
    """:func:`ssm_decode_step` of layer ``layer`` (a scalar) on the stacked
    caches ``{"conv": [L,B,W-1,ch], "state": [L,B,H,P,N]}``: reads the
    layer's conv window and state, and writes the new ones back at
    ``layer``.  Returns (y, the caches).

    A layer scan carries the caches and calls this per layer, so that no
    stack leaves the scan as its output: donated, they are written in
    place, where a scan that returned every layer's state would restack it
    and copy the stack into the donated buffer on every step."""
    conv = jax.lax.dynamic_index_in_dim(caches["conv"], layer,
                                        keepdims=False)
    with jax.named_scope("repro.ssm.state"):
        state = jax.lax.dynamic_index_in_dim(caches["state"], layer,
                                             keepdims=False)
    y, conv, state = ssm_decode_step(params, x, conv, state, cfg)
    conv = jax.lax.dynamic_update_index_in_dim(caches["conv"], conv, layer, 0)
    with jax.named_scope("repro.ssm.state"):
        state = jax.lax.dynamic_update_index_in_dim(caches["state"], state,
                                                    layer, 0)
    return y, {"conv": conv, "state": state}
