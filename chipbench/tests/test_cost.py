"""The operation and byte counts of one decode step, against counts made
by hand from the published sizes."""

import json
from pathlib import Path

import pytest

import run as harness

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _load(name):
    hp = json.loads((CONFIGS / f"{name}.json").read_text())
    return hp, harness.load_module(
        harness.BENCH / "cost" / f"{hp['reference']}.py", f"cost_{name}")


def test_qwen3_counts():
    hp, cost = _load("qwen3-1.7b")
    # per layer: q and o 2048x2048, k and v 2048x1024, MLP 3 x 2048x6144
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144
    matmul = 28 * layer + 2048 * 151936          # tied head, real vocab
    assert matmul == 1_720_451_072
    norms = 28 * (2 * 2048 + 2 * 128) + 2048
    assert cost.param_bytes(hp) == 2 * (matmul + norms) == 3_441_149_952
    # attention at position 0 reads one key and one value per head
    assert cost.token_flops(hp, 0) == 2 * matmul + 4 * 28 * 16 * 128
    flops, nbytes = cost.step_cost(hp, 32, 255)
    assert flops == 32 * (2 * matmul + 4 * 28 * 16 * 128 * 256)
    kv_row = 2 * 28 * 32 * 8 * 128 * 2           # K and V, 32 rows, bf16
    assert nbytes == 3_441_149_952 + kv_row * 256 + kv_row + 32 * 151936 * 2
    assert nbytes == 4_394_067_968


def test_mamba2_counts():
    hp, cost = _load("mamba2-2.7b")
    # in_proj 2560 -> z, x (5120 each), B, C (128 each), dt (80 heads)
    layer = 2560 * (2 * 5120 + 2 * 128 + 80) + 5120 * 2560
    matmul = 64 * layer + 2560 * 50280           # vocab padded to 16
    assert matmul == 2_700_349_440
    small = 64 * (4 * 5376 + 5376 + 3 * 80 + 5120 + 2560) + 2560
    assert cost.param_bytes(hp) == 2 * (matmul + small) == 5_405_158_400
    ssm = 5 * 80 * 64 * 128 + 2 * 4 * 5376
    assert cost.token_flops(hp, 7) == 2 * matmul + 64 * ssm
    flops, nbytes = cost.step_cost(hp, 16, 100)
    assert flops == 16 * (2 * matmul + 64 * ssm)
    state = 64 * 16 * (80 * 64 * 128 + 3 * 5376)  # f32 state + conv window
    assert nbytes == 5_405_158_400 + 2 * 4 * state + 16 * 50280 * 2
    assert nbytes == 10_907_597_056


@pytest.mark.parametrize("name", ["qwen3-1.7b", "mamba2-2.7b"])
def test_live_prefix_never_exceeds_the_whole_cache(name):
    """Bytes grow with the position (qwen3's cache) or stay (mamba2's
    state), never shrink."""
    hp, cost = _load(name)
    b = [cost.step_cost(hp, 8, p)[1] for p in (0, 100, 511)]
    assert b[0] <= b[1] <= b[2]
