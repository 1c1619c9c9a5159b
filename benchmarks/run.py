"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--outdir DIR]

Prints each table and a final ``name,us_per_call,derived`` CSV, and writes
one machine-readable ``BENCH_<name>.json`` per bench next to the CSV so
the performance trajectory (throughput / energy / SLO attainment) is
trackable across commits instead of living in scrollback.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys

from benchmarks import (bench_breakdown, bench_cluster, bench_elastic,
                        bench_fig4_general, bench_fig4_ml, bench_fleet,
                        bench_kernel, bench_kernels, bench_obs,
                        bench_planner, bench_predictor, bench_reachability,
                        bench_regret, bench_router, bench_serving,
                        bench_slo, bench_tpu_pod)

#: Bump when the BENCH_<name>.json layout changes incompatibly;
#: ``benchmarks/compare.py`` refuses baselines from another schema.
SCHEMA_VERSION = 1

BENCHES = {
    "fig4_general": bench_fig4_general.run,   # paper Fig. 4a-4d
    "fig4_ml": bench_fig4_ml.run,             # paper Fig. 4e-4h
    "predictor": bench_predictor.run,         # paper §5.2.2 table
    "reachability": bench_reachability.run,   # paper Fig. 3 + §4.2 example
    "planner": bench_planner.run,             # compiled graph vs seed Alg. 3
    "breakdown": bench_breakdown.run,         # paper Tables 3-4
    "kernels": bench_kernels.run,             # Pallas kernel paths
    "tpu_pod": bench_tpu_pod.run,             # the TPU adaptation, end-to-end
    "fleet": bench_fleet.run,                 # multi-GPU fleet routing
    "serving": bench_serving.run,             # request-level LLM serving SLOs
    "slo": bench_slo.run,                     # SLO-aware vs reactive growth
    "elastic": bench_elastic.run,             # scale-down + plan-ahead gates
    "cluster": bench_cluster.run,             # cluster-of-fleets zone routing
    "obs": bench_obs.run,                     # flight-recorder overhead bound
    "kernel": bench_kernel.run,               # event-kernel events/sec gates
    "router": bench_router.run,               # routing index dispatches/sec
    "regret": bench_regret.run,               # all arms vs the offline oracle
}


def git_sha() -> str:
    """Short SHA of the working tree, or 'unknown' outside a checkout."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=pathlib.Path(__file__).resolve().parent)
        if out.returncode == 0:
            return out.stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        pass
    return "unknown"


def _write_json(outdir: pathlib.Path, name: str,
                rows: list[tuple[str, float, str]], extra,
                sha: str) -> None:
    payload: dict = {
        "bench": name,
        "schema_version": SCHEMA_VERSION,
        "git_sha": sha,
        "rows": [{"name": n, "us_per_call": us, "derived": derived}
                 for n, us, derived in rows],
    }
    if isinstance(extra, dict):
        payload.update(extra)
    path = outdir / f"BENCH_{name}.json"
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    ap.add_argument("--outdir", default=".",
                    help="where BENCH_<name>.json files land")
    ap.add_argument("--trace", default=None, metavar="OUT.jsonl",
                    help="also record one traced SLO serving run and write "
                         "its flight-recorder JSONL here (inspect with "
                         "'python -m repro.obs.report OUT.jsonl')")
    args = ap.parse_args()
    outdir = pathlib.Path(args.outdir)
    # --outdir may name a directory that does not exist yet (CI passes
    # bench-results/ on a fresh checkout) — create it before any write
    outdir.mkdir(parents=True, exist_ok=True)
    sha = git_sha()
    rows: list[tuple[str, float, str]] = []
    failures = []
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        rows_before = len(rows)
        try:
            extra = fn(rows)
        except Exception as e:  # keep the harness running
            failures.append((name, repr(e)))
            print(f"\n!! bench {name} failed: {e!r}")
            continue
        _write_json(outdir, name, rows[rows_before:], extra, sha)
    if args.trace:
        bench_obs.trace_serving_run(args.trace)
    print("\n=== CSV ===")
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if failures:
        print(f"\n{len(failures)} bench(es) failed: "
              f"{[f[0] for f in failures]}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
