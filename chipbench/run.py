#!/usr/bin/env python3
"""The on-chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout that holds ``src/repro``.  Everything a
cell needs is found by name: its configuration (``configs/<config>.json``),
whose ``reference`` names the plain reference (``ref/<reference>.py``) and
the operation counts (``cost/<reference>.py``); its traffic mix
(``mixes/<traffic>.json``), whose ``driver`` names the loop that drives the
program (``drivers/<driver>.py``); the limits of its comparison
(``limits/<workload>.json``); and one reader per metric
(``metrics/<metric>.py``).

Set-up (weights made on the device from the seed, one warm batch of the
cell's own shapes, restart included) comes first and is ``setup_s``.  Then
closed loops of whole batches run until ``--seconds`` have passed and the
traffic has completed a whole cycle (every seed serves the same work in a
cycle, in another order); a rate counts all the work over all the time up
to the end of the last batch.
With ``--trace 1`` a profiler trace of whole batches inside the window
feeds the per-layer metrics.  After the window the served tokens of a
sample of requests are compared with the reference, and the run prints
each compared number beside its limit on stderr, then one JSON line on
stdout.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.

``--control 1`` puts the control in the program's place for the
comparison: the tokens that the reference computed one precision lower
(float8 where the configuration serves bfloat16) puts first, at the
positions the program served.  Such a run must come out not correct; its
readings, with the program's own gap logged beside them, set a cell's
limit.  ``--seed`` takes a comma-separated list too: the seeds run one
after another in one process, which warms up once, and each prints its
line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: traced seconds inside the window: whole batches until at least this
TRACE_SECONDS = 2.0


class Failure(RuntimeError):
    """The run cannot give a result: exit non-zero, print no JSON line."""


def load_module(path: Path, name: str | None = None):
    if not path.is_file():
        raise Failure(f"no file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name or path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str) -> dict:
    """The cell's entry and every file it names, by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Failure(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((BENCH / "mixes" / f"{cell['traffic']}.json")
                     .read_text())
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]
    return {"cell": cell, "config": config, "mix": mix, "limits": limits,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)],
            "driver": BENCH / "drivers" / f"{mix['driver']}.py",
            "reference": BENCH / "ref" / f"{config['reference']}.py",
            "cost": BENCH / "cost" / f"{config['reference']}.py"}


def program_config(config: dict, smoke: bool = False):
    """The program's configuration, checked field by field against the
    configuration file's ``program_fields``.  A field the program lacks
    reads ``None`` and departs like any other."""
    from repro.configs import get_config, get_smoke_config
    name = config["program"]
    cfg = get_smoke_config(name) if smoke else get_config(name)
    wrong = {k: (getattr(cfg, k, None), v)
             for k, v in config["program_fields"].items()
             if getattr(cfg, k, None) != v}
    if wrong:
        raise Failure(f"{name}: the program runs (program, file) {wrong}")
    return cfg


class Context:
    """What a driver and the metric readers get."""

    def __init__(self, files: dict, seed: int, devices, smoke: bool = False):
        self.seed, self.devices = seed, devices
        self.config, self.mix = files["config"], files["mix"]
        self.smoke = smoke
        self.reference = load_module(files["reference"],
                                     "ref." + files["reference"].stem)
        self.cost = load_module(files["cost"], "cost_" + files["cost"].stem)

    def key(self, stream: int):
        """A PRNG key from the seed (any size) and a stream number."""
        import jax
        import numpy as np
        word = np.random.SeedSequence([self.seed, stream]).generate_state(1)
        return jax.random.PRNGKey(int(word[0]))

    def program_config(self):
        return program_config(self.config, self.smoke)


def _device_info(devices) -> dict:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": (max(peaks) if None not in peaks
                                  else None)}


class Run:
    """One run's readings, as the metric readers see them."""

    def __init__(self, ctx, driver, records, t0, t1, setup_s, peak_bytes,
                 peaks, chips, trace=None):
        self.ctx, self.driver, self.records = ctx, driver, records
        self.window_s = t1 - t0
        self.setup_s, self.peak_bytes = setup_s, peak_bytes
        self.peaks, self.chips = peaks, chips
        self.trace = trace
        self._analysis = None

    def analysis(self) -> dict | None:
        """The traced window, the chips' timelines, the step program and
        each of its executions with its position; None without a device
        trace."""
        if not (self.trace and self.trace["devices"]):
            return None
        if self._analysis is None:
            import trace_reduce as tr
            t0, t1 = tr.window(self.trace)
            dev = self.trace["devices"][0]
            step = tr.step_program(dev, t0, t1)
            self._analysis = {
                "t0": t0, "t1": t1, "devices": self.trace["devices"],
                "step": step,
                "steps": self.driver.trace_steps(
                    self.trace["traced"], self.trace, dev, step, t0, t1)}
        return self._analysis


def _compile_counter():
    """Counts of programs loaded (compiled or read from the cache) and of
    cache misses, for the check that nothing compiles in the window."""
    from jax import monitoring
    counts = {"loaded": 0, "load_s": 0.0, "compiled": 0, "on": False}

    def on_duration(name, secs, **_kw):
        if counts["on"] and name == "/jax/core/compile/backend_compile_duration":
            counts["loaded"] += 1
            counts["load_s"] += secs

    def on_event(name, **_kw):
        if counts["on"] and name == "/jax/compilation_cache/cache_misses":
            counts["compiled"] += 1
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return counts


def configure_jax():
    """Puts the program on the path and keeps JAX's compilation cache and
    the TPU runtime's logs inside the checkout; returns ``jax``.  The cache
    has a directory per platform, so that programs compiled by CPU runs of
    the harness's tests never reach a run on the chip."""
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    logs = ROOT / ".chipbench" / "logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))
    import jax
    # the backend starts here; the cache opens at the first compile
    platform = jax.devices()[0].platform
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".chipbench" / "jax_cache" / platform))
    # every program, however quick to compile, is read back in later runs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


#: cells whose programs this process has loaded: warmed up once per process
_WARMED: set[str] = set()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             files: dict | None = None, require_tpu: bool = True,
             smoke: bool = False, control: bool = False, t_start=None,
             log=None) -> tuple[dict, list]:
    """Runs one cell; returns the result line and the compared numbers.
    ``setup_s`` counts from ``t_start`` (the run's own start by default)."""
    t_start = time.monotonic() if t_start is None else t_start
    log = log or (lambda m: print(f"[chipbench] {m}", file=sys.stderr))
    files = files or resolve(workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise Failure(f"no program: {ROOT / 'src' / 'repro'} is missing")
    jax = configure_jax()

    chips = files["cell"]["chips"]
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise Failure(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise Failure(f"the cell asks for {chips} chips, JAX found "
                      f"{len(devices)}")
    devices = devices[:chips]
    kind = devices[0].device_kind
    peak_table = json.loads((BENCH / "peaks.json").read_text())
    if require_tpu and kind not in peak_table:
        raise Failure(f"no peaks for device kind {kind!r} in peaks.json")
    peaks = peak_table.get(kind)
    log(f"device {devices[0].platform} {kind} x{len(devices)}")

    ctx = Context(files, seed, devices, smoke=smoke)
    driver = load_module(files["driver"], "driver_" + files["driver"].stem
                         ).Driver(ctx)
    counts = _compile_counter()
    driver.setup()
    if workload not in _WARMED:
        driver.warm()
        _WARMED.add(workload)
    setup_s = time.monotonic() - t_start
    log(f"set-up {setup_s:.3f} s")

    trace_dir = ROOT / ".chipbench" / "trace" / workload
    traced, profiling, records = [], False, []
    counts["on"] = True
    t0 = time.perf_counter()
    i = 0
    while True:
        if trace and i == 1 and not traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
            profiling = True
        rec = driver.run_batch(i)
        records.append(rec)
        rec["traced"] = profiling
        if profiling:
            traced.append(rec)
            if rec["t_return"] - traced[0]["t_issue"] >= TRACE_SECONDS:
                jax.profiler.stop_trace()
                profiling = False
        i += 1
        if (rec["t_return"] - t0 >= seconds and i % driver.cycle == 0
                and not profiling and (traced or not trace)):
            break
    t1 = records[-1]["t_return"]
    counts["on"] = False
    log(f"window {t1 - t0:.3f} s, {len(records)} batches of "
        f"{[round(r['t_return'] - r['t_issue'], 3) for r in records]} s; "
        f"programs loaded in the window {counts['loaded']} "
        f"({counts['load_s']:.3f} s), compiled {counts['compiled']}")

    info = _device_info(devices)
    events = None
    if trace:
        import trace_reduce
        events = trace_reduce.extract(trace_reduce.find_xplane(str(trace_dir)))
        events["traced"] = traced
    checked = driver.check(records, control=control)
    attempted = sum(r["rows"] for r in records)
    failed = sum(driver.incomplete(r) for r in records)
    compared = [("widest_gap", checked["widest_gap"],
                 files["limits"]["widest_gap"]["limit"]),
                ("incomplete_requests", failed, 0)]
    correct = all(v <= lim for _, v, lim in compared)

    run = Run(ctx, driver, records, t0, t1, setup_s,
              info["memory_peak_bytes"], peaks, chips, trace=events)
    metrics, breakdown = {}, None
    for m in files["per_layer" if trace else "end_to_end"]:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             "metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace and events["devices"]:
        import breakdown as breakdown_lib
        breakdown = breakdown_lib.read(run)
        info["busy_s"], info["window_s"] = breakdown.pop("busy_window")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # strict JSON has no infinity: a gap that is no number prints as "inf"
    result["checks"] = {n: {"value": v if math.isfinite(v) else str(v),
                            "limit": lim}
                        for n, v, lim in compared}
    log(f"served tokens compared {checked['served_tokens']}"
        + (f"; the program's own gap {checked['program_gap']}" if control
           else ""))
    return result, compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for n, seed in enumerate(args.seed):
        try:
            result, compared = run_cell(
                args.workload, seed, args.seconds, bool(args.trace),
                control=bool(args.control), t_start=T_START if n == 0 else None)
        except Failure as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 2
        for name, value, limit in compared:
            print(f"check {name} {value} limit {limit}", file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
