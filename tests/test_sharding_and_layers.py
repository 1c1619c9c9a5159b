"""Unit tests: sharding rules/policies, partitionable loss & embedding,
cache specs, HLO parser."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ALL_ARCHS, get_smoke_config
from repro.models import registry
from repro.models.layers import cross_entropy_loss, embed_tokens
from repro.models.module import cast_tree
from repro.sharding.partitioning import (ACT_RULES, PARAM_RULES, POLICIES,
                                         apply_policy, spec_for)


class FakeMesh:
    """Just enough of a Mesh for spec_for (axis names + sizes)."""

    def __init__(self, sizes: dict):
        self.axis_names = tuple(sizes)
        self.devices = np.empty(tuple(sizes.values()), dtype=object)


MESH_1POD = FakeMesh({"data": 16, "model": 16})
MESH_2POD = FakeMesh({"pod": 2, "data": 16, "model": 16})


class TestSpecFor:
    def test_param_fsdp_plus_tp(self):
        # embedding [vocab, d]: vocab->model, embed->data
        spec = spec_for(("vocab", "embed"), MESH_1POD, (262144, 5376),
                        PARAM_RULES)
        assert tuple(spec) == ("model", "data")

    def test_head_fallback_when_not_divisible(self):
        # llama4: 40 heads don't divide 16 -> head_dim takes the axis
        spec = spec_for(("embed", "heads", "head_dim"), MESH_1POD,
                        (5120, 40, 128), PARAM_RULES)
        assert tuple(spec) == ("data", None, "model")

    def test_expert_ffn_fallback_for_grok(self):
        # grok: 8 experts don't divide 16 -> expert_ffn shards over model
        spec = spec_for(("experts", "embed", "expert_ffn"), MESH_1POD,
                        (8, 6144, 32768), PARAM_RULES)
        assert tuple(spec) == (None, "data", "model")

    def test_kv_cache_seq_fallback(self):
        # kv=8 can't shard -> cache_seq takes model (priority order)
        spec = spec_for(("layers", "batch", "cache_seq", "kv_heads",
                         "head_dim"), MESH_1POD,
                        (28, 128, 32768, 8, 128), ACT_RULES)
        assert tuple(spec) == (None, "data", "model", None, None)

    def test_multi_pod_batch(self):
        spec = spec_for(("batch", "seq"), MESH_2POD, (256, 4096), ACT_RULES)
        assert spec[0] == ("pod", "data")

    def test_expert_pod_policy(self):
        prules, _ = apply_policy("expert_pod")
        spec = spec_for(("experts", "embed", "expert_ffn"), MESH_2POD,
                        (128, 5120, 8192), prules)
        assert spec[0] == ("model", "pod")
        assert spec[1] is None           # no d-dim FSDP (§Perf hillclimb 2)
        assert spec[2] == "data"

    def test_all_policies_resolve(self):
        for name in POLICIES:
            prules, arules = apply_policy(name)
            assert "vocab" in prules and "batch" in arules

    @settings(max_examples=30, deadline=None)
    @given(dims=st.tuples(st.integers(1, 4096), st.integers(1, 4096)))
    def test_property_spec_always_valid(self, dims):
        spec = spec_for(("ffn", "embed"), MESH_1POD, dims, PARAM_RULES)
        for axis, dim in zip(spec, dims):
            if axis is not None:
                size = 16
                assert dim % size == 0


class TestPartitionableOps:
    """The §Perf iter-2/3 rewrites must be numerically identical to the
    naive scatter/gather formulations."""

    def test_cross_entropy_matches_naive(self):
        key = jax.random.PRNGKey(0)
        logits = jax.random.normal(key, (2, 8, 64), jnp.float32)
        labels = jax.random.randint(key, (2, 8), 0, 50)
        vocab = 50
        ours = cross_entropy_loss(logits, labels, vocab)
        # naive reference
        masked = logits.at[..., vocab:].set(-1e9)
        logz = jax.scipy.special.logsumexp(masked, axis=-1)
        gold = jnp.take_along_axis(masked, labels[..., None], -1)[..., 0]
        ref = (logz - gold).mean()
        np.testing.assert_allclose(ours, ref, rtol=1e-5)

    def test_cross_entropy_ignores_masked_labels(self):
        logits = jnp.ones((1, 4, 16), jnp.float32)
        labels = jnp.array([[1, 2, -1, -1]])
        l_full = cross_entropy_loss(logits, jnp.array([[1, 2, 3, 4]]), 16)
        l_mask = cross_entropy_loss(logits, labels, 16)
        np.testing.assert_allclose(l_full, l_mask, rtol=1e-6)  # uniform

    def test_onehot_embedding_matches_gather(self):
        cfg = get_smoke_config("qwen3-0.6b")
        params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab)
        x_one = embed_tokens(params, toks, cfg)
        cfg_g = dataclasses.replace(cfg, embed_impl="gather")
        x_gat = embed_tokens(params, toks, cfg_g)
        np.testing.assert_allclose(np.asarray(x_one, np.float32),
                                   np.asarray(x_gat, np.float32),
                                   atol=1e-2)  # bf16 matmul rounding


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_cache_specs_match_structure(arch):
    """The logical axes that place the caches (the pod's and the dry
    run's) have :func:`registry.init_caches`' tree, one name per dim."""
    cfg = get_smoke_config(arch)
    caches = jax.eval_shape(lambda: registry.init_caches(cfg, 2, 64))
    specs = registry.cache_specs(cfg)
    is_spec = lambda x: isinstance(x, tuple)        # noqa: E731
    assert (jax.tree.structure(specs, is_leaf=is_spec)
            == jax.tree.structure(caches))
    for spec, leaf in zip(jax.tree.leaves(specs, is_leaf=is_spec),
                          jax.tree.leaves(caches)):
        assert len(spec) == leaf.ndim, (spec, leaf.shape)


class TestHloParser:
    def test_trip_count_multiplication(self):
        from repro.launch.hlo_parse import analyze
        hlo = """
HloModule test
%body (p: s32[]) -> s32[] {
  %p = s32[] parameter(0)
  %d = f32[8,16]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %a = f32[8,4]{1,0} parameter(1)
  %b = f32[4,16]{1,0} parameter(2)
  ROOT %r = s32[] add(%p, %p)
}
ENTRY %main.1 (x: s32[]) -> s32[] {
  %x = s32[] parameter(0)
  %w = (s32[]) while(%t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = s32[] get-tuple-element(%w), index=0
}
"""
        res = analyze(hlo)
        # dot flops = 2*8*16*4 = 1024, x7 trips
        assert res["flops"] == 1024 * 7

    def test_collective_bytes(self):
        from repro.launch.hlo_parse import analyze
        hlo = """
HloModule test
ENTRY %main.1 (x: f32[16]) -> f32[16] {
  %x = f32[16]{0} parameter(0)
  ROOT %ag = f32[16]{0} all-reduce(%x), replica_groups={}
}
"""
        res = analyze(hlo)
        assert res["collectives"]["all-reduce"] == 64.0


class TestKernelWiring:
    """attn_impl / ssm_impl select the Pallas kernels inside the model."""

    @pytest.mark.parametrize("arch,field", [("qwen3-0.6b", "attn_impl"),
                                            ("gemma3-27b", "attn_impl"),
                                            ("mamba2-2.7b", "ssm_impl")])
    def test_pallas_path_matches_xla(self, arch, field):
        cfg = get_smoke_config(arch)
        params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
        params = cast_tree(params, jnp.float32)
        batch = registry.make_dummy_batch(cfg, 2, 128,
                                          key=jax.random.PRNGKey(7))
        ref = registry.forward(params, cfg, batch).logits
        cfg_p = dataclasses.replace(cfg, **{field: "pallas"})
        out = registry.forward(params, cfg_p, batch).logits
        err = float(jnp.abs(out - ref).max()
                    / (jnp.abs(ref).max() + 1e-9))
        assert err < 5e-3, err

    def test_chunked_arch_falls_back(self):
        """llama4's chunked mask isn't flash-supported: the xla fallback
        must keep the forward correct."""
        cfg = dataclasses.replace(get_smoke_config(
            "llama4-maverick-400b-a17b"), attn_impl="pallas")
        params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
        batch = registry.make_dummy_batch(cfg, 2, 64)
        out = registry.forward(params, cfg, batch)
        assert not bool(jnp.isnan(out.logits).any())
