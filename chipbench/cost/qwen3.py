"""Operations and bytes that one Qwen3 decode step needs, from the shapes.

Counted for what the algorithm needs, not for what the program happens to
do: the bf16 weights are read once (the tied table once, over the real
vocabulary), the live prefix of the KV cache is read (positions up to and
including the one written) and one position is written, and the logits are
written in bf16.  A program that reads the whole preallocated cache, or
pads the vocabulary, spends more than this count and reads as a lower
share of the roofline.
"""

from __future__ import annotations

BF16 = 2


def _matmul_params(hp: dict) -> int:
    d, f, V = hp["hidden_size"], hp["intermediate_size"], hp["vocab_size"]
    qo = hp["num_attention_heads"] * hp["head_dim"]
    kv = hp["num_key_value_heads"] * hp["head_dim"]
    layer = d * qo + 2 * d * kv + qo * d + 3 * d * f
    return hp["num_hidden_layers"] * layer + d * V


def param_bytes(hp: dict) -> int:
    d, L, hd = hp["hidden_size"], hp["num_hidden_layers"], hp["head_dim"]
    norms = L * (2 * d + 2 * hd) + d
    return BF16 * (_matmul_params(hp) + norms)


def token_flops(hp: dict, position: int) -> float:
    """Model operations to produce one token's logits at ``position``."""
    attn = 4 * hp["num_hidden_layers"] * hp["num_attention_heads"] \
        * hp["head_dim"] * (position + 1)
    return 2.0 * _matmul_params(hp) + attn


def step_cost(hp: dict, batch: int, position: int) -> tuple[float, float]:
    """(operations, bytes) of one decode step of ``batch`` rows at
    ``position``."""
    kv = 2 * hp["num_hidden_layers"] * batch * hp["num_key_value_heads"] \
        * hp["head_dim"] * BF16
    nbytes = (param_bytes(hp) + kv * (position + 1) + kv
              + batch * hp["vocab_size"] * BF16)
    return batch * token_flops(hp, position), float(nbytes)
