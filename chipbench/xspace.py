"""The device's ops in a profiler trace, each with what the trace records of
its HLO op: the op's text (the event's name) and its stats, the op's
metadata (``tf_op``, the ``op_name`` that ``jax.named_scope`` writes) among
them where the profiler records it.

``jax.profiler.ProfileData`` gives an event's own stats but not those of
the op the event runs, so this module reads the ``.xplane.pb`` with its own
copy of the part of the XSpace schema it needs
(``tsl/profiler/protobuf/xplane.proto``; field numbers as there).

    python3 chipbench/xspace.py <trace dir> [substring]

prints the device ops whose text or stats hold ``substring`` (all with
none), by device time, with their stats.
"""

from __future__ import annotations

import functools
import glob
import os
import sys

import program_spans as ps
import trace_reduce as tr


@functools.cache
def _classes():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xspace.proto", package="chipbench", syntax="proto2")
    rep, opt = F.LABEL_REPEATED, F.LABEL_OPTIONAL

    def message(name, *fields, oneof=None):
        m = f.message_type.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, ftype, label, *tname in fields:
            field = m.field.add(name=fname, number=number, type=ftype,
                                label=label)
            if tname:
                field.type_name = ".chipbench." + tname[0]
            if oneof and fname.endswith("_value"):
                field.oneof_index = 0

    message("XStat", ("metadata_id", 1, F.TYPE_INT64, opt),
            ("double_value", 2, F.TYPE_DOUBLE, opt),
            ("uint64_value", 3, F.TYPE_UINT64, opt),
            ("int64_value", 4, F.TYPE_INT64, opt),
            ("str_value", 5, F.TYPE_STRING, opt),
            ("bytes_value", 6, F.TYPE_BYTES, opt),
            ("ref_value", 7, F.TYPE_UINT64, opt), oneof="value")
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, opt),
            ("offset_ps", 2, F.TYPE_INT64, opt),
            ("duration_ps", 3, F.TYPE_INT64, opt),
            ("stats", 4, F.TYPE_MESSAGE, rep, "XStat"))
    message("XLine", ("id", 1, F.TYPE_INT64, opt),
            ("name", 2, F.TYPE_STRING, opt),
            ("timestamp_ns", 3, F.TYPE_INT64, opt),
            ("events", 4, F.TYPE_MESSAGE, rep, "XEvent"))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64, opt),
            ("name", 2, F.TYPE_STRING, opt),
            ("stats", 5, F.TYPE_MESSAGE, rep, "XStat"))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64, opt),
            ("name", 2, F.TYPE_STRING, opt))
    # a map<int64, V> is on the wire a repeated {key = 1, value = 2}
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, opt),
            ("value", 2, F.TYPE_MESSAGE, opt, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, opt),
            ("value", 2, F.TYPE_MESSAGE, opt, "XStatMetadata"))
    message("XPlane", ("id", 1, F.TYPE_INT64, opt),
            ("name", 2, F.TYPE_STRING, opt),
            ("lines", 3, F.TYPE_MESSAGE, rep, "XLine"),
            ("event_metadata", 4, F.TYPE_MESSAGE, rep, "EventMetadataEntry"),
            ("stat_metadata", 5, F.TYPE_MESSAGE, rep, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, F.TYPE_MESSAGE, rep, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.XSpace"))


def _stats(stats, names: dict) -> dict:
    out = {}
    for s in stats:
        kind = s.WhichOneof("value")
        if kind is None:
            continue
        value = getattr(s, kind)
        if kind == "ref_value":
            value = names.get(value, "")
        elif kind == "bytes_value":
            continue
        out[names.get(s.metadata_id, str(s.metadata_id))] = value
    return out


def read(path: str) -> dict:
    """``{"devices": [{"name", "ops": [[text, start_ns, duration_ns,
    op_stats, event_stats], ...]}], "batches": [(start_ns, end_ns)]}``:
    each chip's XLA ops, and the harness's ``chipbench.batch`` spans."""
    space = _classes()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    devices, batches = [], []
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        on_device = plane.name.startswith(tr.DEVICE_PREFIX)
        ops, op_stats = [], {}
        for line in plane.lines:
            if on_device and line.name != tr.OPS_LINE:
                continue
            for e in line.events:
                m = meta.get(e.metadata_id)
                name = m.name if m is not None else ""
                start = line.timestamp_ns + e.offset_ps / 1000
                if not on_device:
                    if name == tr.HOST_PREFIX + "batch":
                        batches.append((start, start + e.duration_ps / 1000))
                    continue
                if e.metadata_id not in op_stats:
                    op_stats[e.metadata_id] = (
                        _stats(m.stats, names) if m is not None else {})
                ops.append([name, start, e.duration_ps / 1000,
                            op_stats[e.metadata_id], _stats(e.stats, names)])
        if on_device and ops:
            devices.append({"name": plane.name, "ops": ops})
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "batches": sorted(batches)}


def of_run(run) -> dict | None:
    """:func:`read` of the trace this run wrote, read once and kept on the
    run; None where there is none, or the newest trace under
    ``.chipbench/trace/`` is another run's (its batch spans differ)."""
    if "_xspace" not in vars(run):
        run._xspace = None
        paths = glob.glob(os.path.join(str(ps.TRACE_ROOT), "**",
                                       "*.xplane.pb"), recursive=True)
        if run.trace and run.trace.get("host") and paths:
            got = read(max(paths, key=os.path.getmtime))
            mine = [(s, s + d) for n, s, d in run.trace["host"]
                    if n == tr.HOST_PREFIX + "batch"]
            if [(round(a), round(b)) for a, b in got["batches"]] == \
                    [(round(a), round(b)) for a, b in mine]:
                run._xspace = got
    return run._xspace


def mentions(op: list, text: str) -> bool:
    """Whether an op's text or any of its stats holds ``text``."""
    return text in op[0] or any(text in str(v) for st in op[3:5]
                                for v in st.values())


if __name__ == "__main__":
    if len(sys.argv) not in (2, 3):
        sys.exit(f"usage: {sys.argv[0]} <trace dir> [substring]")
    want = sys.argv[2] if len(sys.argv) == 3 else ""
    space = read(tr.find_xplane(sys.argv[1]))
    for dev in space["devices"]:
        total: dict[str, list] = {}
        for op in dev["ops"]:
            if mentions(op, want) and not op[0].startswith(tr.CONTAINERS):
                key = tr.op_name(op[0])
                total.setdefault(key, [0.0, 0, op])
                total[key][0] += op[2]
                total[key][1] += 1
        print(f"{dev['name']}: {len(total)} ops")
        for key, (ns, n, op) in sorted(total.items(),
                                       key=lambda kv: -kv[1][0])[:40]:
            print(f"  {ns / 1e9:10.6f} s {n:6d}x {key}\n"
                  f"      op {op[3]}\n      event {op[4]}")
