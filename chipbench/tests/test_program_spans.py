"""The program's spans in a traced run (``program_spans.py``) and the four
readers of the program's spans and counters: on a small trace recorded
here with the program's span names, on a slice of a batch recorded on a
TPU v5 lite, on hand-made spans, device ops and records, and on a whole
traced run of each cell at the smoke size."""

import json
import time
import types
from pathlib import Path

import jax
import pytest

import program_spans as ps
import run as harness
import smoke
import trace_reduce as tr

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]
NEW = ["engine.host_step_ms", "predictor.host_ms", "compile.batch_s",
       "engine.ttft_p95_s"]


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               "metric_" + name.replace(".", "_"))


def _ann(name, **args):
    return jax.profiler.TraceAnnotation(name, **args)


def _record(trace_dir, program_spans=True):
    """One batch span around one decode loop of two steps, each a sync and
    a predictor span, as the harness and the program name them."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        with _ann("chipbench.batch"):
            if program_spans:
                with _ann("repro.engine.decode", steps=2):
                    for _ in range(2):
                        with _ann("repro.engine.sync"):
                            time.sleep(0.002)
                        time.sleep(0.001)
                        with _ann("repro.engine.predictor"):
                            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    return tr.find_xplane(str(trace_dir))


def _run(trace):
    return types.SimpleNamespace(trace=trace, records=[])


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    monkeypatch.setattr(ps, "TRACE_ROOT", tmp_path)
    path = _record(tmp_path / "cell")
    return _run(tr.extract(path)), path


def test_spans_of_the_run_and_their_readers(recorded):
    run, path = recorded
    evs = ps.spans(run)
    assert [e[0] for e in evs] == [
        "chipbench.batch", "repro.engine.decode", "repro.engine.sync",
        "repro.engine.predictor", "repro.engine.sync",
        "repro.engine.predictor"]
    assert evs[1][3] == {"steps": 2}
    assert ps.spans(run) is evs
    decode = evs[1]
    syncs = ps.named(evs, "repro.engine.sync", within=decode)
    preds = ps.named(evs, "repro.engine.predictor")
    host = (decode[2] - decode[1] - sum(e - s for _, s, e, _ in syncs)) / 2
    assert host > 1e6
    assert _reader("engine.host_step_ms").read(run) == \
        pytest.approx(host / 1e6)
    assert _reader("predictor.host_ms").read(run) == pytest.approx(
        sum(e - s for _, s, e, _ in preds) / 2 / 1e6)


def test_a_trace_not_the_runs_or_without_program_spans_gives_nothing(
        recorded, tmp_path):
    run, _ = recorded
    _record(tmp_path / "later")
    assert ps.spans(run) is None
    bare = _run(tr.extract(_record(tmp_path / "bare", program_spans=False)))
    assert ps.spans(bare) is None
    for name in ("engine.host_step_ms", "predictor.host_ms"):
        assert _reader(name).read(_run(None)) is None
        assert _reader(name).read(bare) is None


def test_segments_self_time_and_idle_by_span():
    def s(name, a, b):
        return [name, a, b, {}]
    evs = [s("chipbench.batch", 0, 100), s("repro.serve.attempt", 5, 95),
           s("repro.engine.setup", 5, 8), s("repro.engine.replay", 10, 35),
           s("repro.engine.load", 10, 30), s("repro.engine.decode", 40, 90),
           s("repro.engine.sync", 50, 60), s("repro.engine.predictor", 62, 64),
           s("repro.engine.sync", 70, 80)]
    assert ps.segments(evs) == [
        (0, 5, "chipbench.batch"), (5, 8, "repro.engine.setup"),
        (8, 10, "repro.serve.attempt"), (10, 30, "repro.engine.load"),
        (30, 35, "repro.engine.replay"), (35, 40, "repro.serve.attempt"),
        (40, 50, "repro.engine.decode"), (50, 60, "repro.engine.sync"),
        (60, 62, "repro.engine.decode"), (62, 64, "repro.engine.predictor"),
        (64, 70, "repro.engine.decode"), (70, 80, "repro.engine.sync"),
        (80, 90, "repro.engine.decode"), (90, 95, "repro.serve.attempt"),
        (95, 100, "chipbench.batch")]
    decode = evs[5]
    assert ps.self_ns(decode, ps.named(evs, "repro.engine.sync",
                                       within=decode)) == 30
    dev = {"ops": [["a", 0, 2], ["b", 30, 22], ["c", 58, 3], ["d", 96, 1]]}
    idle = ps.idle_by_span(evs, dev, 0, 105)
    assert idle == {"chipbench.batch": 7, "repro.engine.setup": 3,
                    "repro.serve.attempt": 7, "repro.engine.load": 20,
                    "repro.engine.sync": 16, "repro.engine.decode": 17,
                    "repro.engine.predictor": 2, ps.OUTSIDE: 5}
    assert sum(idle.values()) == sum(b - a for a, b in
                                     tr.idle_gaps(dev, 0, 105))


def test_idle_by_span_on_a_recorded_restart():
    """A slice of a qwen3-1.7b.chat-restart batch recorded on a TPU v5
    lite, from 60 ms before its restart to 40 ms after the restarted
    engine's load: the device waits on that load."""
    d = json.loads((Path(__file__).parent / "data" / "restart_spans.json")
                   .read_text())
    (t0, t1), dev = d["slice"], d["devices"][0]
    idle = ps.idle_by_span(d["spans"], dev, t0, t1)
    assert sum(idle.values()) == sum(b - a for a, b in
                                     tr.idle_gaps(dev, t0, t1)) == 550_776_899
    assert max(idle, key=idle.get) == "repro.engine.load"
    assert idle["repro.engine.load"] == 540_057_564
    assert idle["repro.engine.sync"] == 7_128_660
    assert ps.OUTSIDE not in idle
    under = sum(v for k, v in idle.items() if k.startswith(ps.PREFIX))
    assert under / sum(idle.values()) > 0.999


def _records():
    def req(first):
        return types.SimpleNamespace(token_times=[first, first + 1.0])
    return [{"t_issue": 0.0, "t_return": 10.0, "traced": False,
             "requests": [req(0.1 * k) for k in range(1, 21)]},
            {"t_issue": 10.0, "t_return": 20.0, "traced": True,
             "requests": [req(19.0)]},
            {"t_issue": 20.0, "t_return": 30.0, "traced": False,
             "requests": [req(20.5)]}]


def test_ttft_reads_the_untraced_batches():
    run = types.SimpleNamespace(records=_records())
    # 21 first-token waits 0.1..2.0 and 0.5: the 20th of 21 is 1.9
    assert _reader("engine.ttft_p95_s").read(run) == pytest.approx(1.9)
    for rec in run.records:
        rec["requests"] = [types.SimpleNamespace(generated=[1])]
    assert _reader("engine.ttft_p95_s").read(run) is None


def test_compile_seconds_per_untraced_batch(monkeypatch):
    from repro.obs import spans
    log = spans.CompileLog()
    event = spans.COMPILE_EVENTS[2]
    log._ring.extend([(5.0, event, 2.0, "decode_step"),
                      (15.0, event, 3.0, "decode_step"),
                      (24.5, spans.COMPILE_EVENTS[0], 0.5, "decode_step"),
                      (25.0, event, 1.0, "decode_step")])
    monkeypatch.setattr(spans, "compile_log", lambda: log)
    run = types.SimpleNamespace(records=_records())
    assert _reader("compile.batch_s").read(run) == pytest.approx(1.5)
    # a program without the compile log
    monkeypatch.setitem(__import__("sys").modules, "repro.obs.spans", None)
    assert _reader("compile.batch_s").read(run) is None


@pytest.mark.parametrize("workload", CELLS)
def test_traced_smoke_run_reports_the_new_metrics(workload):
    result, _ = harness.run_cell(
        workload, 2 ** 31 + 17, 0.5, True, files=smoke.smoke_files(workload),
        require_tpu=False, smoke=True, log=lambda m: None)
    assert result["correct"], result["checks"]
    for name in NEW:
        assert result["metrics"][name]["value"] > 0, name
