"""The plain references agree with the program's own forward pass at the
smoke size, with both in float32 on the same weights (in the program's
parametrization, as the serving driver hands them over)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as harness
import smoke
from drivers.serve import input_scale, reparametrize
from ref import mamba2, qwen3

REFS = {"qwen3": qwen3, "mamba2": mamba2}


def _setup(name):
    from repro.models import registry
    config = json.loads((harness.BENCH / "configs" / f"{name}.json")
                        .read_text())
    hp = smoke.smoke_config(config)
    cfg = smoke.program_config(config)
    rows = registry.abstract_params(cfg)[0]["embedding"].shape[0]
    return REFS[hp["reference"]], hp, cfg, rows


@pytest.mark.parametrize("name", ["qwen3-1.7b", "mamba2-2.7b"])
def test_reference_matches_program_forward(name):
    from repro.models import registry
    ref, hp, cfg, rows = _setup(name)
    if "norm_epsilon" in hp:
        # the program's RMSNorm epsilon (1e-6) departs from Mamba-2's
        # published 1e-5; with both the same, the rest of the map must agree
        hp["norm_epsilon"] = cfg.norm_eps
    shapes = registry.abstract_params(cfg)[0]
    w = jax.jit(lambda k: ref.init_weights(k, hp, rows))(jax.random.PRNGKey(5))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), w) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), shapes)
    b, t = 2, 40
    # prompts from the published vocabulary, never the table's padding rows
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, hp["vocab_size"], (b, t)), jnp.int32)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    with jax.default_matmul_precision("highest"):
        prog = registry.forward(reparametrize(w32, input_scale(cfg)), cfg,
                                {"tokens": toks}).logits
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    got = ref.make_logits_at(hp)(w, toks, pos)
    assert got.shape == (b, t, hp["vocab_size"])
    # float32 on both sides: only summation order differs
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(prog[..., :hp["vocab_size"]]),
                               atol=1e-4 * float(jnp.abs(got).max()))


@pytest.mark.parametrize("name", ["qwen3-1.7b", "mamba2-2.7b"])
def test_positions_pick_rows(name):
    """``positions`` selects the logits of those positions, row by row."""
    ref, hp, cfg, rows = _setup(name)
    w = jax.jit(lambda k: ref.init_weights(k, hp, rows))(jax.random.PRNGKey(1))
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, hp["vocab_size"], (2, 12)), jnp.int32)
    f = ref.make_logits_at(hp)
    full = f(w, toks, jnp.broadcast_to(jnp.arange(12), (2, 12)))
    some = f(w, toks, jnp.asarray([[3, 11], [0, 5]], jnp.int32))
    np.testing.assert_allclose(some[0], full[0, [3, 11]], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(some[1], full[1, [0, 5]], rtol=1e-5, atol=1e-5)
