"""The control (the tokens that the reference computed with float8 weights
and activations, one step below the configuration's bfloat16, puts first)
in the program's place fails the cell's own limit: a whole run of the
harness at the smoke size, on three seeds, comes out not correct.  On the
chip the same runs, at each cell's own size, set the upper end of the
cell's limit (``run.py --control 1``)."""

import json

import pytest

import run as harness
import smoke

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload, seed):
    files = smoke.smoke_files(workload)
    limit = json.loads((harness.BENCH / "limits" / f"{workload}.json")
                       .read_text())["widest_gap"]["limit"]
    assert files["limits"]["widest_gap"]["limit"] == limit
    result, compared = harness.run_cell(
        workload, seed, 0.5, False, files=files, require_tpu=False,
        smoke=True, control=True, log=lambda m: None)
    assert result["failed"] == 0
    assert dict((n, v) for n, v, _ in compared)["widest_gap"] > limit
    assert not result["correct"], result["checks"]
