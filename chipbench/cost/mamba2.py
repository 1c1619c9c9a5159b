"""Operations and bytes that one Mamba-2 decode step needs, from the shapes.

The bf16 weights are read once (the tied table once, over the real
vocabulary); the float32 SSM state and the convolution's window are read
and written once per layer and row, as the configuration keeps them; the
logits are written in bf16.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def _vocab(hp: dict) -> int:
    return hp.get("padded_vocab_size", hp["vocab_size"])


def _dims(hp: dict):
    d_inner = hp["expand"] * hp["d_model"]
    n, p = hp["d_state"], hp["headdim"]
    return d_inner, d_inner // p, p, n


def _matmul_params(hp: dict) -> int:
    d, V = hp["d_model"], _vocab(hp)
    d_inner, h, _, n = _dims(hp)
    layer = d * (2 * d_inner + 2 * n + h) + d_inner * d
    return hp["n_layer"] * layer + d * V


def param_bytes(hp: dict) -> int:
    d, L, w = hp["d_model"], hp["n_layer"], hp["d_conv"]
    d_inner, h, _, n = _dims(hp)
    small = L * ((w + 1) * (d_inner + 2 * n) + 3 * h + d_inner + d) + d
    return BF16 * (_matmul_params(hp) + small)


def token_flops(hp: dict, position: int) -> float:
    """Model operations for one token: the projections, the convolution,
    and the state update (decay, outer product, add) and read-out."""
    d_inner, h, p, n = _dims(hp)
    ssm = 5 * h * p * n + 2 * hp["d_conv"] * (d_inner + 2 * n)
    return 2.0 * _matmul_params(hp) + hp["n_layer"] * ssm


def step_cost(hp: dict, batch: int, position: int) -> tuple[float, float]:
    """(operations, bytes) of one decode step of ``batch`` rows."""
    d_inner, h, p, n = _dims(hp)
    state = hp["n_layer"] * batch * (h * p * n
                                     + (hp["d_conv"] - 1) * (d_inner + 2 * n))
    nbytes = (param_bytes(hp) + 2 * F32 * state
              + batch * _vocab(hp) * BF16)
    return batch * token_flops(hp, position), float(nbytes)
