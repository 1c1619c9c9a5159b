"""Training/serving substrate tests: optimizer, microbatching equivalence,
checkpoint round-trip, data pipeline, serving engine early restart, MoE
dispatch invariants."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.configs.base import reduce_for_smoke
from repro.models import registry
from repro.models.moe import moe_layer
from repro.models.module import cast_tree
from repro.serving.engine import EngineConfig, Request, ServeEngine
from repro.training.checkpoint import load_checkpoint, save_checkpoint
from repro.training.data import DataConfig, SyntheticLM
from repro.training.optimizer import (AdamWConfig, adamw_update,
                                      global_norm, init_opt_state, lr_at)
from repro.training.train_step import init_train_state, make_train_step


class TestOptimizer:
    def test_lr_schedule_shape(self):
        cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
        lrs = [float(lr_at(cfg, jnp.int32(s))) for s in (0, 5, 10, 50, 100)]
        assert lrs[0] == 0.0
        assert lrs[1] == pytest.approx(5e-4)
        assert lrs[2] == pytest.approx(1e-3, rel=0.2)
        assert lrs[4] == pytest.approx(1e-4, rel=0.05)  # min ratio

    def test_grad_clipping(self):
        params = {"w": jnp.ones((4,), jnp.float32)}
        huge = {"w": jnp.full((4,), 1e6, jnp.float32)}
        state = init_opt_state(params)
        cfg = AdamWConfig(grad_clip_norm=1.0, warmup_steps=1, lr=0.1,
                          weight_decay=0.0)
        _, _, info = adamw_update(params, huge, state, cfg)
        assert float(info["grad_norm"]) > 1e5  # pre-clip norm reported

    def test_bf16_moments_update(self):
        params = {"w": jnp.ones((8,), jnp.bfloat16)}
        state = init_opt_state(params, moments_dtype=jnp.bfloat16)
        grads = {"w": jnp.full((8,), 0.1, jnp.bfloat16)}
        # lr large enough that the step survives bf16 rounding (~0.4%)
        newp, newstate, _ = adamw_update(params, grads, state,
                                         AdamWConfig(lr=0.5,
                                                     warmup_steps=1))
        assert newstate["m"]["w"].dtype == jnp.bfloat16
        assert newp["w"].dtype == jnp.bfloat16
        assert not np.allclose(np.asarray(newp["w"], np.float32), 1.0)

    def test_global_norm(self):
        t = {"a": jnp.full((4,), 3.0), "b": jnp.full((4,), 4.0)}
        assert float(global_norm(t)) == pytest.approx(10.0)


class TestMicrobatching:
    def test_microbatch_equivalence(self):
        """Grad accumulation over k microbatches == full-batch step
        (f32 params; identical data)."""
        cfg = reduce_for_smoke(get_smoke_config("qwen3-0.6b"))
        data = SyntheticLM(cfg, DataConfig(batch=8, seq=32, seed=0))
        batch = next(data.batches())
        opt = AdamWConfig(warmup_steps=1, total_steps=10)

        losses = {}
        for k in (1, 4):
            state, _ = init_train_state(jax.random.PRNGKey(0), cfg)
            state["params"] = cast_tree(state["params"], jnp.float32)
            step = jax.jit(make_train_step(cfg, opt, n_microbatches=k))
            _, metrics = step(state, batch)
            losses[k] = float(metrics["loss"])
        assert losses[1] == pytest.approx(losses[4], rel=1e-4)


class TestCheckpoint:
    def test_roundtrip_bf16_and_nested(self):
        state = {
            "params": {"w": jnp.arange(8, dtype=jnp.bfloat16),
                       "nested": [jnp.ones((2, 2), jnp.float32)]},
            "step": jnp.int32(7),
        }
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(f"{d}/ck.npz", state, step=7)
            back = load_checkpoint(f"{d}/ck.npz", jax.device_get(state))
        assert back["params"]["w"].dtype.name == "bfloat16"
        np.testing.assert_array_equal(
            np.asarray(back["params"]["w"], np.float32),
            np.arange(8, dtype=np.float32))
        np.testing.assert_array_equal(back["params"]["nested"][0],
                                      np.ones((2, 2), np.float32))


class TestDataPipeline:
    def test_deterministic_and_learnable(self):
        cfg = get_smoke_config("qwen3-0.6b")
        a = next(SyntheticLM(cfg, DataConfig(4, 32, seed=5)).batches())
        b = next(SyntheticLM(cfg, DataConfig(4, 32, seed=5)).batches())
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        # labels are next-token shifted
        gen = SyntheticLM(cfg, DataConfig(2, 16, seed=1))
        batch = next(gen.batches())
        assert batch["tokens"].shape == (2, 16)
        assert batch["labels"].shape == (2, 16)

    def test_frontend_tensors_for_stub_families(self):
        for arch, key in (("whisper-medium", "frames"),
                          ("pixtral-12b", "patches")):
            cfg = get_smoke_config(arch)
            batch = next(SyntheticLM(cfg, DataConfig(2, 16)).batches())
            assert key in batch


class TestServeEngine:
    def test_generates_and_records_memory(self):
        cfg = get_smoke_config("qwen3-0.6b")
        params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(cfg, params, EngineConfig(max_batch=2,
                                                    max_context=64))
        reqs = [Request(uid=i, prompt=np.arange(4, dtype=np.int32),
                        max_new_tokens=8) for i in range(2)]
        out = eng.run(reqs)
        assert all(len(r.generated) == 8 for r in out)
        req, reuse = eng.accountant.series()
        assert len(req) >= 8
        assert all(0 < r <= 1 for r in reuse)

    def test_engine_reuse_resets_per_run_state(self):
        """A second batch on the same engine must start with fresh
        accounting: no inherited live watermark (which suppressed the first
        iteration's allocation) and no converged predictor state."""
        cfg = get_smoke_config("qwen3-0.6b")
        params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(cfg, params, EngineConfig(max_batch=2,
                                                    max_context=64))
        def reqs():
            return [Request(uid=i, prompt=np.arange(4, dtype=np.int32),
                            max_new_tokens=8) for i in range(2)]
        eng.run(reqs())
        first = [s.requested_bytes for s in eng.accountant.history]
        n_first = len(first)
        eng.run(reqs())
        second = [s.requested_bytes for s in eng.accountant.history]
        # history was reset, not appended to
        assert len(second) == n_first
        # identical batch => identical allocation series; before the fix the
        # second run's first iteration missed the live-delta allocation
        assert second == pytest.approx(first, rel=1e-6)

        # with prediction on, the predictor must also restart per run
        # (a partition large enough that no early restart fires)
        pred_eng = ServeEngine(cfg, params,
                               EngineConfig(max_batch=2, max_context=64,
                                            partition_gb=1e3))
        pred_eng.run(reqs())
        n_obs = len(pred_eng.predictor.req_mem_list)
        pred_eng.run(reqs())
        assert len(pred_eng.predictor.req_mem_list) == n_obs  # not doubled

    def test_early_restart_raised_on_tiny_partition(self):
        from repro.core.restart import NeedsLargerPartition
        cfg = get_smoke_config("qwen3-0.6b")
        params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(cfg, params,
                          EngineConfig(max_batch=1, max_context=96,
                                       partition_gb=1e-4))
        with pytest.raises(NeedsLargerPartition):
            eng.run([Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                             max_new_tokens=80)])


class TestMoEDispatch:
    def _run(self, b=2, s=64, e=4, k=2, cap_factor=2.0):
        import dataclasses
        cfg = get_smoke_config("grok-1-314b")
        cfg = dataclasses.replace(cfg, n_experts=e, top_k=k,
                                  capacity_factor=cap_factor)
        from repro.models.moe import init_moe
        from repro.models.module import ParamBuilder
        pb = ParamBuilder(jax.random.PRNGKey(0))
        init_moe(pb, cfg)
        params, _ = pb.build()
        x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model),
                              jnp.float32) * 0.1
        return moe_layer(params, x, cfg), cfg

    def test_output_shape_and_finite(self):
        (out, aux), cfg = self._run()
        assert out.shape == (2, 64, cfg.d_model)
        assert bool(jnp.isfinite(out).all())
        assert float(aux) > 0.0

    def test_aux_loss_lower_bound(self):
        """Switch load-balance loss >= 1 at uniform routing, > for skew."""
        (_, aux), _ = self._run()
        assert float(aux) >= 0.99

    @settings(max_examples=10, deadline=None)
    @given(k=st.integers(1, 2), e=st.sampled_from([2, 4]))
    def test_property_capacity_drops_bounded(self, k, e):
        """With capacity_factor >= e/k... generous capacity, the layer is
        (close to) lossless: zero tokens dropped => output differs from a
        lower-capacity run."""
        (out_hi, _), _ = self._run(e=e, k=k, cap_factor=8.0)
        assert bool(jnp.isfinite(out_hi).all())
