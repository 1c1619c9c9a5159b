"""Every cell of BENCHMARK.json finds its files by name, and the file keeps
the shape later checks rely on."""

import json
import re

import pytest

import run as harness
import traffic

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    files = harness.resolve(cell)
    for key in ("driver", "reference", "cost"):
        assert files[key].is_file(), files[key]
        harness.load_module(files[key], f"probe_{key}")
    for m in files["end_to_end"] + files["per_layer"]:
        mod = harness.load_module(harness.BENCH / "metrics" / f"{m['name']}.py",
                                  "probe_metric")
        assert callable(mod.read)
    names = {m["name"] for m in files["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert files["per_layer"]
    assert files["limits"]["widest_gap"]["limit"] > 0
    # the traffic generator accepts the mix, and its lengths fit the context
    mix = files["mix"]
    assert max(traffic.prompt_levels(mix)) + mix["new_tokens"][1] \
        <= mix["max_context"]


def test_names_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for c in BENCH["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells


def test_every_seed_gets_the_same_sizes():
    mix = json.loads((harness.BENCH / "mixes" / "chat-restart.json")
                     .read_text())
    a, b = traffic.Traffic(mix, 1, 1000), traffic.Traffic(mix, 2 ** 33, 1000)
    la = [a.batch(i).prompts.shape[1] for i in range(8)]
    lb = [b.batch(i).prompts.shape[1] for i in range(8)]
    assert sorted(la) == sorted(lb) and la != lb
    assert sum(la[:2]) == sum(la[2:4]) == sum(lb[:2])
    assert sorted(a.batch(8).new_tokens) == traffic.new_token_levels(mix)
