"""The reduction from trace to numbers, on a slice of a trace recorded on a
TPU v5 lite: one qwen3-1.7b.chat-restart batch, two steps before its first
restart and two after, with the ops merged to busy intervals.  The batch
span is cut to the slice, so positions count from the slice's start."""

import json
from pathlib import Path

import pytest

import breakdown
import run as harness
import trace_reduce as tr

EVENTS = json.loads((Path(__file__).parent / "data" / "trace_slice.json")
                    .read_text())
DEV = EVENTS["devices"][0]
STEP = "jit__lambda(15436576247152643245)"
#: the four step executions of the slice, as recorded (ns)
STEP_DURS = [18_785_003, 18_780_971, 18_783_413, 18_778_967]


def _analysis():
    driver = harness.load_module(harness.BENCH / "drivers" / "serve.py",
                                 "serve_driver").Driver
    t0, t1 = tr.window(EVENTS)
    rec = {"rows": 32, "prompt_len": 224}
    steps = driver.trace_steps(None, [rec], EVENTS, DEV, STEP, t0, t1)
    return {"t0": t0, "t1": t1, "devices": [DEV], "step": STEP,
            "steps": steps}


def test_window_busy_and_idle():
    t0, t1 = tr.window(EVENTS)
    assert t1 - t0 == 3_690_173_818 - 3_338_551_547 == 351_622_271
    # the intervals are disjoint, so busy time is their plain sum
    assert tr.busy_ns(DEV, t0, t1) == sum(d for _, _, d in DEV["ops"]) \
        == 78_180_485
    gaps = tr.idle_gaps(DEV, t0, t1)
    assert sum(b - a for a, b in gaps) == 351_622_271 - 78_180_485
    # the longest: the restarted engine's step program traced and loaded
    assert max(b - a for a, b in gaps) == 3_652_589_559 - 3_386_650_338


def test_union_clips_and_merges():
    dev = {"ops": [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 40, 20]]}
    assert tr.union(((s, d) for _, s, d in dev["ops"]), 2, 50) == \
        [(2, 15), (30, 35), (40, 50)]
    assert tr.busy_ns(dev, 2, 50) == 13 + 5 + 10
    assert tr.idle_gaps(dev, 0, 70) == [(15, 30), (35, 40), (60, 70)]


def test_step_program_and_positions():
    t0, t1 = tr.window(EVENTS)
    assert tr.step_program(DEV, t0, t1) == STEP
    a = _analysis()
    assert [s["dur"] for s in a["steps"]] == STEP_DURS
    assert [(s["attempt"], s["position"]) for s in a["steps"]] == \
        [(0, 0), (0, 1), (1, 0), (1, 1)]
    mean_ms = sum(STEP_DURS) / 4 / 1e6
    assert mean_ms == pytest.approx(18.7820885)


def test_gap_labels():
    gaps = breakdown.gap_labels(_analysis(), DEV)
    restart = gaps["restart: engine rebuilt on the larger slice"]
    # the 266 ms wait for the new engine, the 5.1 ms before its caches
    assert restart >= (265_939_221 + 5_138_451) / 1e9
    assert sum(gaps.values()) == pytest.approx(273_441_786 / 1e9)


def test_step_roofline_on_the_slice():
    """Positions 0 and 1, 32 rows: bytes bound.  Least time per step is
    (params + live K/V read + one position written + logits) / 819e9."""
    hp = json.loads((harness.BENCH / "configs" / "qwen3-1.7b.json")
                    .read_text())
    cost = harness.load_module(harness.BENCH / "cost" / "qwen3.py", "c")
    kv = 2 * 28 * 32 * 8 * 128 * 2
    least = [(3_441_149_952 + kv * (p + 1) + kv + 32 * 151936 * 2) / 819e9
             for p in (0, 1, 0, 1)]
    for p, want in zip((0, 1), least):
        assert max(x / y for x, y in zip(cost.step_cost(hp, 32, p),
                                         (197e12, 819e9))) == \
            pytest.approx(want)
    share = 100 * sum(least) / (sum(STEP_DURS) / 1e9)
    assert share == pytest.approx(22.494, abs=1e-3)


class _Run:
    """The slice as a traced run, as the metric readers see it."""

    def __init__(self):
        self.ctx = type("Ctx", (), {})()
        self.ctx.config = json.loads(
            (harness.BENCH / "configs" / "qwen3-1.7b.json").read_text())
        self.ctx.cost = harness.load_module(harness.BENCH / "cost" / "qwen3.py",
                                            "c")
        self.peaks = json.loads((harness.BENCH / "peaks.json").read_text()
                                )["TPU v5 lite"]

    def analysis(self):
        return _analysis()


@pytest.mark.parametrize("metric, want", [
    ("step.device_ms", 18.7820885),
    ("step_roofline", 22.494),
    ("device.idle_share", 100 * 273_441_786 / 351_622_271),
])
def test_metric_readers_on_the_slice(metric, want):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py",
                                 "m")
    assert reader.read(_Run()) == pytest.approx(want, abs=1e-3)
