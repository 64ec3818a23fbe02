"""Device seconds by ``jax.named_scope`` word, for the readers of a share
of busy time under one scope. The scope of an ``XLA Ops`` event is the
``tf_op`` stat of the event's *metadata*, which ``ProfileData`` (what
``trace_reduce.py`` reads with) does not show: the trace is read as the
``XSpace`` protocol buffer, as ``tools/trace_event_stats.py`` does by hand
(PERF.md, Findings PR 25), here with the few fields it needs declared to
``google.protobuf`` directly (importing TensorFlow for its generated module
costs a traced run 15 s). An operation counts under a word if the word is
anywhere on its path. A ``while`` operation spans its body's operations,
so only operations that are not loops themselves are counted."""

from __future__ import annotations

import glob
import os

from benchmarks import trace_reduce

_OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench_out")
_cache: dict = {}


def _newest_trace_dir():
    dirs = [d for d in glob.glob(os.path.join(_OUT, "*.t1.trace")) if os.path.isdir(d)]
    return max(dirs, key=os.path.getmtime) if dirs else None


def seconds_under(word: str) -> float | None:
    """Device seconds under the scope ``word`` on the first chip of the
    newest traced run under ``bench_out/`` (the run that is reading), or
    None where there is no trace or no device plane in it."""
    trace_dir = _newest_trace_dir()
    if trace_dir is None:
        return None
    if trace_dir not in _cache:
        _cache[trace_dir] = _by_path(trace_dir)
    paths = _cache[trace_dir]
    if paths is None:
        return None
    return sum(s for path, s in paths.items() if word in path.split("/"))


def _xspace_class():
    """``XSpace`` of tsl/profiler/protobuf/xplane.proto, with the fields
    read here (a map is its repeated key/value entries on the wire)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    fd = descriptor_pb2.FileDescriptorProto(
        name="fei_bench_xplane.proto", package="fei_bench_xplane", syntax="proto3")
    T = descriptor_pb2.FieldDescriptorProto

    def msg(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, num, ftype, rep, tname in fields:
            m.field.add(name=fname, number=num, type=ftype, type_name=tname,
                        label=T.LABEL_REPEATED if rep else T.LABEL_OPTIONAL)

    ref = ".fei_bench_xplane."
    msg("XStat", ("metadata_id", 1, T.TYPE_INT64, 0, None),
        ("str_value", 5, T.TYPE_STRING, 0, None),
        ("ref_value", 7, T.TYPE_UINT64, 0, None))
    msg("XStatMetadata", ("id", 1, T.TYPE_INT64, 0, None),
        ("name", 2, T.TYPE_STRING, 0, None))
    msg("XEventMetadata", ("id", 1, T.TYPE_INT64, 0, None),
        ("name", 2, T.TYPE_STRING, 0, None),
        ("stats", 5, T.TYPE_MESSAGE, 1, ref + "XStat"))
    msg("XEvent", ("metadata_id", 1, T.TYPE_INT64, 0, None),
        ("duration_ps", 3, T.TYPE_INT64, 0, None))
    msg("XLine", ("name", 2, T.TYPE_STRING, 0, None),
        ("events", 4, T.TYPE_MESSAGE, 1, ref + "XEvent"))
    msg("EventMetaEntry", ("key", 1, T.TYPE_INT64, 0, None),
        ("value", 2, T.TYPE_MESSAGE, 0, ref + "XEventMetadata"))
    msg("StatMetaEntry", ("key", 1, T.TYPE_INT64, 0, None),
        ("value", 2, T.TYPE_MESSAGE, 0, ref + "XStatMetadata"))
    msg("XPlane", ("name", 2, T.TYPE_STRING, 0, None),
        ("lines", 3, T.TYPE_MESSAGE, 1, ref + "XLine"),
        ("event_metadata", 4, T.TYPE_MESSAGE, 1, ref + "EventMetaEntry"),
        ("stat_metadata", 5, T.TYPE_MESSAGE, 1, ref + "StatMetaEntry"))
    msg("XSpace", ("planes", 1, T.TYPE_MESSAGE, 1, ref + "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("fei_bench_xplane.XSpace"))


def _by_path(trace_dir: str):
    """``{scope path (tf_op): device seconds}`` of the first chip."""
    try:
        path = trace_reduce.find_xplane(trace_dir)
        space = _xspace_class()()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
    except Exception:  # noqa: BLE001 - nothing this reader can read
        return None
    for plane in space.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        metas = {e.key: e.value for e in plane.event_metadata}
        totals: dict = {}
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                meta = metas[ev.metadata_id]
                if trace_reduce.base_name(trace_reduce.short_name(meta.name)) \
                        in ("while", "conditional"):
                    continue
                op = ""
                for s in meta.stats:
                    if names.get(s.metadata_id) == "tf_op":
                        op = s.str_value or names.get(s.ref_value, "")
                totals[op] = totals.get(op, 0.0) + ev.duration_ps * 1e-12
        return totals  # the first chip
    return None


def share_of_busy(ctx: dict, word: str):
    tr = ctx["trace"]
    if not tr["devices"] or not tr.get("busy_s"):
        return None
    seconds = seconds_under(word)
    if not seconds:
        return None
    return 100.0 * seconds / tr["busy_s"]
