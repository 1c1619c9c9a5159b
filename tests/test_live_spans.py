"""The live serving path's spans, token stamps and compile log, on the CPU.

One ``serve_with_early_restart`` call of the qwen3-1.7b smoke model,
started on a slice small enough that the predictor restarts it once, is
recorded under the JAX profiler and read back from the ``.xplane.pb``; a
second ``run`` on the engine that finished is recorded with it.  The
program's stamps (``Request.token_times``, the compile log) are on
``time.perf_counter()``; an anchor span opened at a known
``perf_counter()`` puts them on the profiler's clock.
"""

import glob
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.launch.mesh import host_pod_backend
from repro.launch.serve import serve_with_early_restart
from repro.models import registry
from repro.obs.spans import COMPILE_EVENTS, CompileLog, compile_log, span
from repro.serving.engine import Request

TRACE_EVENT = COMPILE_EVENTS[0]


def _requests(cfg, n=2, new=12):
    rng = np.random.default_rng(0)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 4)
                    .astype(np.int32), max_new_tokens=new) for i in range(n)]


def _host_spans(trace_dir):
    """``[name, start_ns, end_ns, args]`` of the ``repro.*`` and ``test.*``
    host spans, by start."""
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [[e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)] for e in line.events
                    if e.name.startswith(("repro.", "test."))]
    return sorted(out, key=lambda s: (s[1], -s[2]))


class Recorded:
    def __init__(self, spans, anchor_s, plain, res, again, backend):
        self.spans, self.plain, self.res = spans, plain, res
        self.again, self.backend = again, backend
        start = self.named("test.anchor")[0][1]
        self._offset_ns = start - anchor_s * 1e9

    def named(self, name, within=None):
        return [s for s in self.spans if s[0] == name
                and (within is None or within[1] <= s[1] <= s[2] <= within[2])]

    def ns(self, t):
        """A ``perf_counter()`` reading on the profiler's clock."""
        return t * 1e9 + self._offset_ns


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    cfg = get_smoke_config("qwen3-1.7b")
    params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
    backend = host_pod_backend(["d0"])

    def serve():
        return serve_with_early_restart(cfg, params, _requests(cfg),
                                        backend=backend, max_context=64,
                                        partition_gb=1e-4, log=lambda m: None)
    plain = serve()
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        anchor_s = time.perf_counter()
        with jax.profiler.TraceAnnotation("test.anchor"):
            pass
        res = serve()
        again = res.engine.run(_requests(cfg))
    finally:
        jax.profiler.stop_trace()
    return Recorded(_host_spans(trace_dir), anchor_s, plain, res, again,
                    backend)


def test_span_tree(recorded):
    attempts = recorded.named("repro.serve.attempt")
    assert [a[3] for a in attempts] == [
        {"attempt": 0, "slice_gb": 1e-4, "rows": 2},
        {"attempt": 1, "slice_gb": recorded.backend.profiles[0].mem_gb,
         "rows": 2}]
    restart, = recorded.named("repro.serve.restart")
    assert attempts[0][2] <= restart[1] <= restart[2] <= attempts[1][1]
    assert restart[3] == {"from_gb": 1e-4,
                          "to_gb": recorded.backend.profiles[0].mem_gb}
    for a in attempts:
        for name in ("setup", "load", "replay", "decode"):
            assert len(recorded.named(f"repro.engine.{name}", a)) == 1, name
        replay, = recorded.named("repro.engine.replay", a)
        assert replay[3] == {"positions": 4}
        assert len(recorded.named("repro.engine.load", replay)) == 1
        setup, = recorded.named("repro.engine.setup", a)
        decode, = recorded.named("repro.engine.decode", a)
        assert setup[2] <= replay[1] and replay[2] <= decode[1]
        assert decode[3] == {"steps": 12}


def test_one_sync_and_one_predictor_span_per_decode_step(recorded):
    attempts = recorded.named("repro.serve.attempt")
    counts = []
    for a in attempts:
        decode, = recorded.named("repro.engine.decode", a)
        syncs = recorded.named("repro.engine.sync", decode)
        assert len(recorded.named("repro.engine.predictor", decode)) \
            == len(syncs)
        counts.append(len(syncs))
    # the first attempt stops at the step whose predictor restarts it; the
    # last serves every asked-for token
    assert 0 < counts[0] < 12 and counts[1] == 12
    assert len(recorded.named("repro.engine.sync")) == sum(counts) + 12


def test_token_times_stamp_each_token_inside_the_last_attempt(recorded):
    last = recorded.named("repro.serve.attempt")[-1]
    for r in recorded.res.requests:
        assert len(r.token_times) == len(r.generated) == r.max_new_tokens
        assert r.token_times == sorted(r.token_times)
        assert last[1] < recorded.ns(r.token_times[0])
        assert recorded.ns(r.token_times[-1]) < last[2]


def test_compile_log_sees_each_new_engine_load_and_no_second_run(recorded):
    log = compile_log()
    loads = recorded.named("repro.engine.load")
    # each attempt builds an engine, the second run reuses the last one
    assert len(loads) == 2
    traced = [(recorded.ns(end), fun) for end, event, _, fun in
              log.events(0.0, float("inf")) if event == TRACE_EVENT]
    for _, s0, s1, _ in loads:
        assert any(s0 <= t <= s1 and fun == "decode_step"
                   for t, fun in traced)
    second = [s for s in recorded.spans
              if s[0] in ("repro.engine.replay", "repro.engine.decode")
              and s[1] > recorded.named("repro.serve.attempt")[-1][2]]
    assert len(second) == 2
    for _, s0, s1, _ in second:
        assert not [e for e in log.events(0.0, float("inf"))
                    if s0 <= recorded.ns(e[0]) <= s1]
    assert log.totals[TRACE_EVENT][0] >= len(loads)


def test_served_tokens_do_not_depend_on_the_profiler(recorded):
    assert [r.generated for r in recorded.res.requests] \
        == [r.generated for r in recorded.plain.requests]
    assert [r.generated for r in recorded.again] \
        == [r.generated for r in recorded.plain.requests]


def test_compile_seconds_count_nested_events_once():
    log = CompileLog(capacity=3)
    for event, seconds in [(COMPILE_EVENTS[0], 1.0), (COMPILE_EVENTS[1], 2.0),
                           (COMPILE_EVENTS[2], 3.0), ("/jax/other", 9.0)]:
        log.record(event, seconds, fun_name="f")
    assert [e[1] for e in log.events(0.0, float("inf"))] == \
        list(COMPILE_EVENTS)
    assert log.totals[COMPILE_EVENTS[2]] == [1, 3.0]
    # hand-made: a 4 s compile ending at 10 with a 1 s trace inside it, and
    # a 1 s one ending at 20
    log._ring.clear()
    log._ring.extend([(9.5, COMPILE_EVENTS[0], 1.0, "inner"),
                      (10.0, COMPILE_EVENTS[2], 4.0, "outer"),
                      (20.0, COMPILE_EVENTS[2], 1.0, "next")])
    assert log.seconds(0.0, 30.0) == pytest.approx(5.0)
    assert log.seconds(0.0, 15.0) == pytest.approx(4.0)


def test_span_is_a_profiler_annotation_under_the_repro_prefix(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("unit", k=3):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [s[0::3] for s in _host_spans(tmp_path)] == [["repro.unit",
                                                        {"k": 3}]]
