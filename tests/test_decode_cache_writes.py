"""Every attention decode step writes one cache position per layer.

Each step reads every layer's cache and writes one position into the
stacked [L, B, C, KH, hd] self-attention caches.  A layer scan that
returns the whole layer cache restacks (copies) all of it on every step,
which on a TPU costs several times the step's own reading of the cache;
these tests look for that pattern in the step's jaxpr, in each layout
with attention caches: the plain layer scan (qwen3), interleaved MoE pairs
(llama4), the hybrid's shared attention (zamba2) and the encoder-decoder's
self-attention (whisper).
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import registry

BATCH, CONTEXT = 2, 16
#: arch -> the cache whose stack is written per step
STACKS = {"qwen3-0.6b": "k", "llama4-maverick-400b-a17b": "k",
          "zamba2-7b": "attn_k", "whisper-medium": "k"}


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (scan, pjit, ...) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.fixture(scope="module", params=list(STACKS))
def step_jaxpr(request):
    """(the stacked self-attention cache's shape, [L, B, C, KH, hd], and
    the decode step's jaxpr)."""
    cfg = get_smoke_config(request.param)
    params = registry.abstract_params(cfg)[0]
    caches = jax.eval_shape(
        lambda: registry.init_caches(cfg, BATCH, CONTEXT))
    jaxpr = jax.make_jaxpr(
        lambda p, t, i, c: registry.decode_step(p, cfg, t, i, c))(
        params, jax.ShapeDtypeStruct((BATCH, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32), caches)
    return tuple(caches[STACKS[request.param]].shape), jaxpr.jaxpr


def test_layer_scan_returns_no_cache(step_jaxpr):
    stacked, jaxpr = step_jaxpr
    layer_cache = stacked[1:]
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]
    assert scans
    for eqn in scans:
        shapes = [tuple(v.aval.shape) for v in eqn.outvars]
        assert not any(s[-4:] == layer_cache for s in shapes), shapes


def test_caches_written_one_position_at_a_time(step_jaxpr):
    stacked, jaxpr = step_jaxpr
    writes = [e for e in _eqns(jaxpr)
              if e.primitive.name == "dynamic_update_slice"
              and tuple(e.invars[0].aval.shape[-4:]) == stacked[1:]]
    # k and v: the stacked caches themselves take the new position
    assert sum(tuple(e.invars[0].aval.shape) == stacked
               for e in writes) >= 2
    for eqn in writes:
        update = eqn.invars[1].aval.shape
        assert update[-3] == 1, update
