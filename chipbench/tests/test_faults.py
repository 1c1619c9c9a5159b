"""The whole run without the chip, at the smoke size: a sound run comes
out correct, and each fault planted under the timed path makes ``correct``
false."""

import json

import jax.numpy as jnp
import pytest

import run as harness
import smoke

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _run(workload, seed=2 ** 31 + 11):
    result, compared = harness.run_cell(
        workload, seed, 0.5, False, files=smoke.smoke_files(workload),
        require_tpu=False, smoke=True, log=lambda m: None)
    return result


def _state_unchanged(monkeypatch):
    from repro.models import registry
    step = registry.decode_step

    def frozen(params, cfg, token, index, caches):
        logits, _ = step(params, cfg, token, index, caches)
        return logits, caches
    monkeypatch.setattr(registry, "decode_step", frozen)


def _token_altered(monkeypatch):
    from repro.models import registry
    step = registry.decode_step

    def shifted(params, cfg, token, index, caches):
        logits, caches = step(params, cfg, token, index, caches)
        return jnp.roll(logits, 1, axis=-1), caches
    monkeypatch.setattr(registry, "decode_step", shifted)


def _half_batch(monkeypatch):
    from repro.serving.engine import ServeEngine
    serve = ServeEngine.run

    def half(self, requests):
        serve(self, requests[:len(requests) // 2])
        return requests
    monkeypatch.setattr(ServeEngine, "run", half)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    result = _run(workload)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert {"tokens_per_s", "setup_s"} <= set(result["metrics"])


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered,
                                   _half_batch])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_caught(workload, fault, monkeypatch):
    fault(monkeypatch)
    result = _run(workload)
    assert not result["correct"], result["checks"]
