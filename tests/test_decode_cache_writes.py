"""Every attention decode step writes one cache position per layer.

Each step reads every layer's cache and writes one position into the
stacked [L, B, C, KH, hd] self-attention caches.  A layer scan that
returns the whole layer cache restacks (copies) all of it on every step,
which on a TPU costs several times the step's own reading of the cache;
these tests look for that pattern in the step's jaxpr, in each layout
with attention caches: the plain layer scan (qwen3), interleaved MoE pairs
(llama4), the hybrid's shared attention (zamba2) and the encoder-decoder's
self-attention (whisper).

An SSM's recurrent caches ({conv, state}, stacked over layers) are read
whole and written whole each step; the layer scan carries the stacks and
writes each layer's slice back in place (``ssm.ssm_decode_layer``), so no
scan returns them (mamba2, and zamba2's mamba layers).  The step gives
what the scan that passed them through as its inputs and outputs gave, to
the bit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import attention as attn
from repro.models import registry
from repro.models import ssm as ssm_lib
from repro.models import transformer
from repro.models.hybrid import _group_shape
from repro.models.layers import embed_tokens, mlp, rmsnorm, unembed

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


def _decode_jaxpr(cfg):
    """(the caches' shapes, the decode step's jaxpr)."""
    params = registry.abstract_params(cfg)[0]
    caches = jax.eval_shape(
        lambda: registry.init_caches(cfg, BATCH, CONTEXT))
    jaxpr = jax.make_jaxpr(
        lambda p, t, i, c: registry.decode_step(p, cfg, t, i, c))(
        params, jax.ShapeDtypeStruct((BATCH, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32), caches)
    return caches, jaxpr.jaxpr


@pytest.fixture(scope="module", params=list(STACKS))
def step_jaxpr(request):
    """(the stacked self-attention cache's shape, [L, B, C, KH, hd], and
    the decode step's jaxpr)."""
    caches, jaxpr = _decode_jaxpr(get_smoke_config(request.param))
    return tuple(caches[STACKS[request.param]].shape), jaxpr


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


SSM_ARCHS = ["mamba2-2.7b", "zamba2-7b"]


def _ssm_stacks(caches) -> list:
    """The stacked SSM caches, [L, B, W-1, ch] and [L, B, H, P, N]."""
    return [stack for name, c in caches.items() if name.startswith("ssm")
            for stack in c.values()]


@pytest.mark.parametrize("arch", SSM_ARCHS)
def test_layer_scan_returns_no_ssm_stack(arch):
    caches, jaxpr = _decode_jaxpr(get_smoke_config(arch))
    stacks = {tuple(a.shape) for a in _ssm_stacks(caches)}
    layers = {s[1:] for s in stacks}
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]
    assert scans
    for eqn in scans:
        ys = [tuple(v.aval.shape) for v in
              eqn.outvars[eqn.params["num_carry"]:]]
        assert not any(len(s) > len(layer) and s[-len(layer):] == layer
                       for s in ys for layer in layers), ys
    # each layer's conv window and state go back into the stacks themselves
    written = {tuple(e.invars[0].aval.shape) for e in _eqns(jaxpr)
               if e.primitive.name == "dynamic_update_slice"}
    assert stacks <= written, (stacks, written)


def _parent_mamba2_step(params, cfg, token, index, caches):
    """``transformer.decode_step``'s SSM branch as it was: the stacks go
    through the layer scan as its inputs and come back as its outputs."""
    x = embed_tokens(params, token, cfg)
    dtype = x.dtype

    def body(h, xs):
        lp, conv_c, state_c = xs
        out, conv_c, state_c = ssm_lib.ssm_decode_step(
            lp, transformer._ssm_input(h, lp, cfg, dtype), conv_c, state_c,
            cfg)
        return h + out, (conv_c, state_c)

    x, (conv, state) = jax.lax.scan(
        body, transformer._residual(x, cfg),
        (params["layers"], caches["ssm"]["conv"], caches["ssm"]["state"]))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps).astype(dtype)
    return unembed(params, x, cfg), {"ssm": {"conv": conv, "state": state}}


def _parent_zamba2_step(params, cfg, token, index, caches):
    """``hybrid.decode_step`` as it was: the mamba layers' stacks go
    through the group and layer scans as inputs and outputs."""
    x = embed_tokens(params, token, cfg)
    n_groups, remainder = _group_shape(cfg)
    m = max(cfg.attn_every, 1)
    shared = params["shared_attn"]

    def mamba_step(h, xs):
        lp, conv_c, state_c = xs
        out, conv_c, state_c = ssm_lib.ssm_decode_step(
            lp, rmsnorm(h, lp["norm1"], cfg.norm_eps), conv_c, state_c, cfg)
        return h + out, (conv_c, state_c)

    def grouped(a):
        return a.reshape((n_groups, m) + a.shape[1:])

    def group_body(h, xs):
        lp_group, conv_cg, state_cg, ck, cv = xs
        h, (conv_cg, state_cg) = jax.lax.scan(
            mamba_step, h, (lp_group, conv_cg, state_cg))
        out, k_new, v_new = attn.mha_decode_append(
            shared, rmsnorm(h, shared["norm1"], cfg.norm_eps), cfg, ck, cv,
            index)
        h = h + out
        h = h + mlp(shared, rmsnorm(h, shared["norm2"], cfg.norm_eps), cfg)
        return h, (conv_cg, state_cg, k_new, v_new)

    x, (conv_g, state_g, k_new, v_new) = jax.lax.scan(
        group_body, x,
        (jax.tree_util.tree_map(grouped, params["mamba_layers"]),
         grouped(caches["ssm"]["conv"]), grouped(caches["ssm"]["state"]),
         caches["attn_k"], caches["attn_v"]))
    new = attn.write_positions(caches, {"attn_k": k_new, "attn_v": v_new},
                               index)
    new["ssm"] = {
        "conv": conv_g.reshape((n_groups * m,) + conv_g.shape[2:]),
        "state": state_g.reshape((n_groups * m,) + state_g.shape[2:]),
    }
    if remainder:
        x, (conv_t, state_t) = jax.lax.scan(
            mamba_step, x,
            (params["mamba_tail"], caches["ssm_tail"]["conv"],
             caches["ssm_tail"]["state"]))
        new["ssm_tail"] = {"conv": conv_t, "state": state_t}
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, x, cfg), new


@pytest.mark.parametrize("arch, tail", [
    ("mamba2-2.7b", False), ("zamba2-7b", False), ("zamba2-7b", True)],
    ids=["mamba2-2.7b", "zamba2-7b", "zamba2-7b-tail"])
def test_ssm_decode_matches_the_scan_that_returned_the_stacks(arch, tail):
    """Three steps from random caches: the same logits and caches, bitwise,
    as the parent's scan; ``tail`` gives zamba2 a remainder layer after its
    groups (3 layers, a shared block every 2), which writes ``ssm_tail``."""
    cfg = get_smoke_config(arch)
    if tail:
        cfg = dataclasses.replace(cfg, n_layers=3, attn_every=2)
    parent = (_parent_zamba2_step if cfg.family == "hybrid"
              else _parent_mamba2_step)
    params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
    shapes = jax.eval_shape(lambda: registry.init_caches(cfg, BATCH, CONTEXT))
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    caches = jax.tree_util.tree_unflatten(tree, [
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])
    assert ("ssm_tail" in caches) == tail
    tokens = jax.random.randint(jax.random.PRNGKey(2), (3, BATCH, 1), 0,
                                cfg.vocab)
    new_step = jax.jit(lambda p, t, i, c: registry.decode_step(p, cfg, t, i,
                                                               c))
    old_step = jax.jit(lambda p, t, i, c: parent(p, cfg, t, i, c))
    got, want = caches, caches
    for i, token in enumerate(tokens):
        index = jnp.int32(i)
        logits, got = new_step(params, token, index, got)
        ref_logits, want = old_step(params, token, index, want)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), got, want)
