"""The live serving path on the CPU: the host's buddy pod, the slice to
device map, the early-restart loop shared by the serve CLI and the chip
smoke test, interpret-mode selection, the compile cache helper, and the
multi-tenant pod on four forced host devices."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.ops import resolve_interpret
from repro.launch import compile_cache
from repro.launch.mesh import host_pod_backend, slice_devices
from repro.launch.serve import serve_with_early_restart
from repro.models import registry
from repro.serving.engine import Request

REPO = Path(__file__).resolve().parents[1]


class TestHostPod:
    def test_one_chip_is_a_1x1_pod(self):
        backend = host_pod_backend(["d0"])
        assert backend.pod_shape == (1, 1)
        assert [p.name for p in backend.profiles] == ["1x1"]

    def test_four_chips_are_a_2x2_pod(self):
        backend = host_pod_backend(["d0", "d1", "d2", "d3"])
        assert backend.pod_shape == (2, 2) and backend.max_depth == 2
        assert [p.name for p in backend.profiles] == ["1x1", "1x2", "2x2"]

    @pytest.mark.parametrize("n", [2, 3, 8])
    def test_other_counts_are_refused(self, n):
        with pytest.raises(ValueError):
            host_pod_backend([f"d{i}" for i in range(n)])

    @pytest.mark.parametrize("handle,want", [
        ((0, 0), [[0]]), ((0, 1), [[1]]), ((1, 0), [[2]]), ((1, 1), [[3]]),
        ((0,), [[0, 1]]), ((1,), [[2, 3]]), ((), [[0, 1], [2, 3]])])
    def test_slice_devices_follow_the_pod_shape(self, handle, want):
        backend = host_pod_backend([0, 1, 2, 3])
        got = slice_devices(backend, handle, [0, 1, 2, 3])
        assert got.tolist() == want

    def test_slice_devices_need_the_whole_pod(self):
        with pytest.raises(ValueError):
            slice_devices(host_pod_backend([0, 1, 2, 3]), (0,), [0, 1, 2])


def _requests(cfg, n=2, new=12):
    rng = np.random.default_rng(0)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 4)
                    .astype(np.int32), max_new_tokens=new) for i in range(n)]


class TestServeWithEarlyRestart:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = get_smoke_config("qwen3-0.6b")
        params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
        return cfg, params

    def test_restarts_once_onto_the_host_slice(self, model):
        cfg, params = model
        backend = host_pod_backend(["d0"])
        plain = serve_with_early_restart(cfg, params, _requests(cfg),
                                         backend=backend, max_context=64)
        assert plain.restarts == []
        res = serve_with_early_restart(cfg, params, _requests(cfg),
                                       backend=backend, max_context=64,
                                       partition_gb=1e-4, log=lambda m: None)
        assert [p.name for p in res.restarts] == ["1x1"]
        assert res.profile_gb == backend.profiles[0].mem_gb
        # checkpointless: the restarted batch is served from its prompts,
        # so its tokens are exactly those of a run that never restarted
        assert ([r.generated for r in res.requests]
                == [r.generated for r in plain.requests])
        assert res.engine.prompt_logits.shape[:2] == (2, 1)

    def test_refuses_a_restart_larger_than_the_host(self, model):
        cfg, params = model
        tiny = host_pod_backend(["d0"], chip_hbm_gb=1e-6)
        with pytest.raises(RuntimeError, match="largest slice of this host"):
            serve_with_early_restart(cfg, params, _requests(cfg),
                                     backend=tiny, max_context=64,
                                     partition_gb=1e-7, log=lambda m: None)


def test_kernels_are_interpreted_off_the_chip_unless_asked():
    assert jax.default_backend() == "cpu"
    assert resolve_interpret(None) is True
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


class TestCompileCache:
    def _run(self, monkeypatch, env):
        was = jax.config.jax_compilation_cache_dir
        if env is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        try:
            return (compile_cache.enable_compile_cache(),
                    jax.config.jax_compilation_cache_dir)
        finally:
            jax.config.update("jax_compilation_cache_dir", was)

    def test_env_wins_and_nothing_is_set(self, monkeypatch, tmp_path):
        was = jax.config.jax_compilation_cache_dir
        got, cfg_dir = self._run(monkeypatch, str(tmp_path))
        assert got == str(tmp_path) and cfg_dir == was

    def test_default_is_fixed_under_the_repo(self, monkeypatch):
        got, cfg_dir = self._run(monkeypatch, None)
        assert got == cfg_dir == str(REPO / ".jax_cache")


def test_multi_tenant_example_on_four_host_devices():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(REPO / "src")}
    out = subprocess.run([sys.executable, str(REPO / "examples" /
                                              "multi_tenant.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300, check=True).stdout
    assert "tenant-a: 24 tokens on 1x1 ([0])" in out
    assert "tenant-b: 24 tokens on 1x1 ([1])" in out
    assert "restarted from 1x1" in out and "on 1x2" in out
    assert "back to empty pod: True" in out
