"""The plain attention decode step writes one cache position per layer.

Each step reads every layer's cache and writes one position into the
stacked [L, B, C, KH, hd] caches.  A layer scan that returns the whole
layer cache restacks (copies) all of it on every step, which on a TPU costs
several times the step's own reading of the cache; these tests look for
that pattern in the step's jaxpr.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import registry

BATCH, CONTEXT = 2, 16


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (scan, pjit, ...) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.fixture(scope="module")
def step_jaxpr():
    cfg = get_smoke_config("qwen3-0.6b")
    params = registry.abstract_params(cfg)[0]
    caches = jax.eval_shape(
        lambda: registry.init_caches(cfg, BATCH, CONTEXT))
    jaxpr = jax.make_jaxpr(
        lambda p, t, i, c: registry.decode_step(p, cfg, t, i, c))(
        params, jax.ShapeDtypeStruct((BATCH, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32), caches)
    layer_cache = (BATCH, CONTEXT, cfg.n_kv_heads, cfg.resolved_head_dim)
    return cfg, jaxpr.jaxpr, layer_cache


def test_layer_scan_returns_no_cache(step_jaxpr):
    cfg, jaxpr, layer_cache = step_jaxpr
    stacked = (cfg.n_layers,) + layer_cache
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]
    assert scans
    for eqn in scans:
        shapes = [tuple(v.aval.shape) for v in eqn.outvars]
        assert stacked not in shapes, shapes


def test_caches_written_one_position_at_a_time(step_jaxpr):
    cfg, jaxpr, layer_cache = step_jaxpr
    writes = [e for e in _eqns(jaxpr)
              if e.primitive.name == "dynamic_update_slice"
              and tuple(e.invars[0].aval.shape[-4:]) == layer_cache]
    # k and v: the stacked caches themselves take the new position
    stacked = (cfg.n_layers,) + layer_cache
    assert sum(tuple(e.invars[0].aval.shape) == stacked
               for e in writes) >= 2
    for eqn in writes:
        update = eqn.invars[1].aval.shape
        assert update[-3] == 1, update
