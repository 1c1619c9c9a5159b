"""Named spans and a compile log for the live serving path, on the
profiler's clock.

The live path (``launch/serve.py``, ``serving/engine.py``) records its
spans as JAX profiler host annotations: they cost about a microsecond each
while no trace is taken, and while one is, they land in the same
``.xplane.pb`` as the device's ops, on the same clock, so each idle gap of
the device can be put down to the span that covers it.  The simulator's
:class:`repro.obs.trace.Tracer` stays on its simulated clock and is not
used here.

Spans, each nested in the one above it on the serving thread, with their
args and what reads them (``chipbench/metrics/<name>.py``; "the idle
table" is ``chipbench/program_spans.py``, which puts each idle gap of the
device down to the innermost span covering it):

- ``repro.serve.attempt`` (``attempt``, ``slice_gb``, ``rows``): one
  attempt of ``serve_with_early_restart``, from building its engine to its
  return or early restart.  The idle table.
- ``repro.serve.restart`` (``from_gb``, ``to_gb``): the restart target
  chosen and logged.  The idle table.
- ``repro.engine.setup`` (``cache_kind``, ``cache_bytes``): caches made,
  prompts sent to the device.  ``cache_kind`` is ``"ssm"`` for an
  attention-free model's recurrent state and ``"kv"`` for a model with a
  KV cache; ``cache_bytes`` is the caches' size on the device.  The idle
  table.
- ``repro.engine.load`` (``program``): an engine's first call of one of
  its programs, ``"prefill"`` or ``"decode"``: trace, lower, compile or
  load, dispatch.  Both lie in ``repro.engine.replay``: after a prefill
  the decode step is compiled there, ahead of its first call.  The idle
  table.
- ``repro.engine.replay`` (``positions``, ``calls``): the prompt's
  ``positions`` written into the caches, by ``calls`` program calls: 1
  where one prefill call writes the cache layout, ``positions`` where the
  prompt is replayed through the decode step.  The idle table.
- ``repro.engine.decode`` (``steps``): the decode loop.
  ``engine.host_step_ms``, with its ``sync`` children.
- ``repro.engine.sync``: one decode step's wait for its token on the host.
  ``engine.host_step_ms``.
- ``repro.engine.predictor``: one decode step's memory bookkeeping,
  predictor and restart decision.  ``predictor.host_ms``.

Device scopes (``jax.named_scope``: the HLO metadata of the ops inside,
so the device trace's ops carry them):

- ``repro.ssm.state`` (``models/ssm.ssm_decode_step`` and
  ``ssm_decode_layer``): the decode step's recurrent-state update (decay,
  outer product, add), its read-out and the ``D`` skip, and the read and
  in-place write of the layer's state in the stacked cache.
  ``ssm.state_ms`` and ``ssm.state_roofline``.

Counters:

- ``Request.token_times`` (``serving/engine.py``): the ``time.perf_counter()``
  at which each generated token reached the host; read by
  ``engine.ttft_p95_s``.
- :func:`compile_log`: every trace, lowering and backend compile (or
  persistent-cache load) of the process, stamped with
  ``time.perf_counter()`` when it ended; read by ``compile.batch_s``, and by
  an operator looking for recompiles in a live server.
"""

from __future__ import annotations

import collections
import threading
import time

import jax

PREFIX = "repro."

#: the compile phases JAX reports, in the order they run
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` with ``args``, for a ``with`` block."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


class CompileLog:
    """The last ``capacity`` compile events, ``(end, event, seconds,
    fun_name)`` with ``end`` on ``time.perf_counter()``, and running
    ``totals`` per event: ``[count, seconds]``.  Phases of one compile
    follow each other, but a function traced inside another's trace
    reports its own trace event inside the outer one's."""

    def __init__(self, capacity: int = 4096) -> None:
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self.totals = {e: [0, 0.0] for e in COMPILE_EVENTS}
        self._lock = threading.Lock()

    def record(self, event: str, seconds: float, **kwargs) -> None:
        if event not in self.totals:
            return
        end = time.perf_counter()
        with self._lock:
            self._ring.append((end, event, seconds,
                               kwargs.get("fun_name", "")))
            self.totals[event][0] += 1
            self.totals[event][1] += seconds

    def events(self, t0: float, t1: float) -> list[tuple]:
        """The kept events that ended in ``[t0, t1]``."""
        with self._lock:
            return [e for e in self._ring if t0 <= e[0] <= t1]

    def seconds(self, t0: float, t1: float) -> float:
        """Wall seconds spent compiling by the events that ended in
        ``[t0, t1]``: the union of their intervals, so that a trace nested
        in another counts once."""
        total, last = 0.0, float("-inf")
        for start, end in sorted((e - s, e) for e, _, s, _ in
                                 self.events(t0, t1)):
            if end > last:
                total += end - max(start, last)
                last = end
        return total


_LOG: CompileLog | None = None
_LOG_LOCK = threading.Lock()


def compile_log() -> CompileLog:
    """The process's compile log; its listener is registered on the first
    call, and the log sees the compiles that follow it."""
    global _LOG
    with _LOG_LOCK:
        if _LOG is None:
            _LOG = CompileLog()
            jax.monitoring.register_event_duration_secs_listener(_LOG.record)
        return _LOG
