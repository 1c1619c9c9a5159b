"""The readers of the SSM state ops (``ssm_state.py``, ``ssm.state_ms``,
``ssm.state_roofline``) and the trace reader under them (``xspace.py``), on
a hand-made trace whose ops carry the program's scope in their op's stats,
as a TPU trace keeps an op's metadata."""

import json
import types

import pytest
from jax.profiler import ProfileData

import run as harness
import ssm_state
import xspace

HP = json.loads((harness.BENCH / "configs" / "mamba2-2.7b.json").read_text())
STATE = "f32[64,16,80,64,128]{4,3,2,1,0:T(8,128)}"


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               "metric_" + name.replace(".", "_"))


def test_least_bytes_at_the_cells_size():
    # 64 layers x 16 rows x 80 heads x 64 x 128 float32, read and written
    assert ssm_state.least_bytes(HP, 16) == 5_368_709_120
    assert ssm_state.dims(HP) == (80, 64, 128)


@pytest.mark.parametrize("text, shapes", [
    (f"%fusion.3 = {STATE} fusion(%a), kind=kLoop",
     [("f32", (64, 16, 80, 64, 128))]),
    ("%fusion.4 = (f32[16,80,64]{2,1,0}, bf16[16,5120]{1,0}) fusion(%a)",
     [("f32", (16, 80, 64)), ("bf16", (16, 5120))]),
    ("%fusion.152 = bf16[32,6144]", [("bf16", (32, 6144))]),
    ("%c = f32[] constant(0)", [("f32", ())]),
])
def test_result_shapes(text, shapes):
    assert ssm_state.result_shapes(text) == shapes


def _trace(ops, scope=True):
    """A device plane of ``ops`` (text, start_ns, duration_ns, scoped) and
    one ``chipbench.batch`` span on the host, as serialized XSpace bytes."""
    metas, events = [], []
    for i, (text, start, dur, scoped) in enumerate(ops, start=10):
        stat = (' stats { metadata_id: 3 str_value: '
                '"jit(decode_step)/while/body/repro.ssm.state/mul" }'
                if scoped and scope else
                ' stats { metadata_id: 3 str_value: "jit(decode_step)/add" }')
        metas.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{text}"{stat} }} }}')
        events.append(f'events {{ metadata_id: {i} offset_ps: {start * 1000}'
                      f' duration_ps: {dur * 1000} }}')
    return ProfileData.text_proto_to_serialized_xspace(f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {" ".join(events)} }}
  {" ".join(metas)}
  stat_metadata {{ key: 3 value {{ id: 3 name: "tf_op" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 99000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "chipbench.batch" }} }} }}
''')


#: two executions of the step program, [100, 200) and [300, 400) ns
OPS = [
    ("%while.1 = (s32[], " + STATE + ") while(%t)", 100, 90, True),
    ("%multiply_reduce_fusion.6 = f32[16,80,64]{2,1,0} fusion(%a)",
     110, 7, True),
    (f"%bitcast_dynamic-update-slice_fusion.2 = {STATE} fusion(%a)",
     120, 11, False),
    ("%fusion.72 = bf16[16,1,10576]{2,0,1} fusion(%a)", 140, 20, False),
    (f"%copy.39 = {STATE} copy(%g)", 180, 13, False),
    ("%multiply_reduce_fusion.6 = f32[16,80,64]{2,1,0} fusion(%a)",
     310, 5, True),
    ("%fusion.72 = bf16[16,1,10576]{2,0,1} fusion(%a)", 320, 50, False),
    ("%dynamic-slice_fusion.1 = f32[16,80,64,128]{3,2,1,0} fusion(%a)",
     500, 40, False),
]


def _run(tmp_path, scope=True):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_trace(OPS, scope))
    steps = [{"start": 100, "dur": 100, "rows": 16},
             {"start": 300, "dur": 100, "rows": 8}]
    run = types.SimpleNamespace(
        analysis=lambda: {"steps": steps},
        ctx=types.SimpleNamespace(config=HP),
        peaks={"hbm_bytes_per_s": 819e9})
    run._xspace = xspace.read(str(path))
    return run


def test_read_keeps_each_ops_stats_and_the_batch_spans(tmp_path):
    space = _run(tmp_path)._xspace
    dev, = space["devices"]
    assert len(dev["ops"]) == len(OPS)
    text, start, dur, op_stats, event_stats = dev["ops"][1]
    assert (text, start, dur) == (OPS[1][0], 110, 7)
    assert op_stats == {
        "tf_op": "jit(decode_step)/while/body/repro.ssm.state/mul"}
    assert event_stats == {}
    assert space["batches"] == [(0, 99000)]


@pytest.mark.parametrize("scope, per_step", [
    # the scoped read-out, the state stack's write and its copy; the loop
    # that encloses them and the projection do not count
    (True, [7 + 11 + 13, 5]),
    # without the scope in the trace the shape alone picks the ops: the
    # read-out's [16, 80, 64] is not the state's shape
    (False, [11 + 13])])
def test_state_ops_per_step(tmp_path, scope, per_step):
    run = _run(tmp_path, scope)
    got = ssm_state.per_step(run)
    if scope:
        assert [ns for _, ns in got] == per_step
    else:
        assert [ns for _, ns in got] == per_step + [0]
    ms = _reader("ssm.state_ms").read(run)
    assert ms == pytest.approx(sum(per_step) / 2 / 1e6)
    share = _reader("ssm.state_roofline").read(run)
    least = (ssm_state.least_bytes(HP, 16) + ssm_state.least_bytes(HP, 8)) \
        / 819e9
    assert share == pytest.approx(100 * least / (sum(per_step) / 1e9))


def test_nothing_to_read_without_state_ops(tmp_path):
    run = _run(tmp_path)
    run._xspace = {"devices": [{"name": "/device:TPU:0", "ops": [
        ["%fusion.72 = bf16[16,1,10576]{2,0,1} fusion(%a)", 140, 20, {}, {}]
    ]}], "batches": []}
    assert ssm_state.per_step(run) is None
    assert _reader("ssm.state_ms").read(run) is None
    assert _reader("ssm.state_roofline").read(run) is None
