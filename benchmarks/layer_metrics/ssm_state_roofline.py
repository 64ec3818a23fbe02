"""Kernels: the mixer's recurrence (the ``ssm_state`` scope: the state
read, decayed, added to, read out and written) as a share of its roofline,
over the step dispatches that lie wholly inside the traced interval. By
scope and not by an operation's name, so that it reads the same work
whether XLA's fusions or a kernel do it.

Least time: what ``kernel_costs/ssm_state.py`` says the dispatch's live
rows need (``state_rows`` of its own flight record: live rows times steps),
bytes over the device's published HBM bandwidth or operations over its
bf16 peak, whichever is longer. Where an admission chunk rides a dispatch
its scan runs under the same scope inside the same dispatch: its seconds
are counted, and its bytes and operations are counted in (``chunk_tokens``
of the record). Divided by the device seconds of the operations under the
scope that start inside those dispatches. A program whose records carry no
``state_rows``, or whose trace has no such scope, gives nothing to read.

The trace is read as ``_scopes.py`` reads it (the scope is the ``tf_op``
stat of an event's metadata), with the two fields that place an event in
time declared besides: a line's ``timestamp_ns`` and an event's
``offset_ps``, which are what ``ProfileData`` turns into ``start_ns``."""

from __future__ import annotations

import sys

from benchmarks import peaks, trace_reduce
from benchmarks.kernel_costs import cost_fn

from . import _scopes
from ._common import clock_offset
from ._spans import dispatches

SCOPE = "ssm_state"


def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    fd = descriptor_pb2.FileDescriptorProto(
        name="fei_bench_xplane_timed.proto", package="fei_bench_xplane_timed",
        syntax="proto3")
    T = descriptor_pb2.FieldDescriptorProto

    def msg(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, num, ftype, rep, tname in fields:
            m.field.add(name=fname, number=num, type=ftype, type_name=tname,
                        label=T.LABEL_REPEATED if rep else T.LABEL_OPTIONAL)

    ref = ".fei_bench_xplane_timed."
    msg("XStat", ("metadata_id", 1, T.TYPE_INT64, 0, None),
        ("str_value", 5, T.TYPE_STRING, 0, None),
        ("ref_value", 7, T.TYPE_UINT64, 0, None))
    msg("XStatMetadata", ("id", 1, T.TYPE_INT64, 0, None),
        ("name", 2, T.TYPE_STRING, 0, None))
    msg("XEventMetadata", ("id", 1, T.TYPE_INT64, 0, None),
        ("name", 2, T.TYPE_STRING, 0, None),
        ("stats", 5, T.TYPE_MESSAGE, 1, ref + "XStat"))
    msg("XEvent", ("metadata_id", 1, T.TYPE_INT64, 0, None),
        ("offset_ps", 2, T.TYPE_INT64, 0, None),
        ("duration_ps", 3, T.TYPE_INT64, 0, None))
    msg("XLine", ("name", 2, T.TYPE_STRING, 0, None),
        ("timestamp_ns", 3, T.TYPE_INT64, 0, None),
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
        pool.FindMessageTypeByName("fei_bench_xplane_timed.XSpace"))


def events_under(trace_dir: str, word: str):
    """[(start seconds on the trace's clock, device seconds)] of the first
    chip's operations (loops left out: they span their bodies) with
    ``word`` on their scope path; None where nothing can be read."""
    try:
        space = _xspace_class()()
        with open(trace_reduce.find_xplane(trace_dir), "rb") as f:
            space.ParseFromString(f.read())
    except Exception:  # noqa: BLE001 - nothing this reader can read
        return None
    for plane in space.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        metas = {e.key: e.value for e in plane.event_metadata}
        under: dict = {}  # metadata id -> is it under the word
        out = []
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                hit = under.get(ev.metadata_id)
                if hit is None:
                    meta = metas[ev.metadata_id]
                    op = ""
                    for s in meta.stats:
                        if names.get(s.metadata_id) == "tf_op":
                            op = s.str_value or names.get(s.ref_value, "")
                    loop = trace_reduce.base_name(
                        trace_reduce.short_name(meta.name)) in ("while", "conditional")
                    hit = under[ev.metadata_id] = \
                        not loop and word in op.split("/")
                if hit:
                    out.append((line.timestamp_ns * 1e-9 + ev.offset_ps * 1e-12,
                                ev.duration_ps * 1e-12))
        return out  # the first chip
    return None


def read(ctx):
    off = clock_offset(ctx)
    trace_dir = _scopes._newest_trace_dir()
    if off is None or trace_dir is None or not ctx["trace"]["devices"]:
        return None
    a, b = ctx["traced"]
    spans = [(t0 + off, t1 + off, r["tags"])
             for t0, t1, r in dispatches(ctx, ("dispatch.step",))
             if t0 >= a and t1 <= b and "state_rows" in r["tags"]]
    if not spans:
        return None
    events = events_under(trace_dir, SCOPE)
    if not events:
        return None
    cost = cost_fn(SCOPE)
    need_bytes = need_flops = scope_s = 0.0
    n = 0
    for lo, hi, tags in spans:
        s = sum(d for t, d in events if lo <= t < hi)
        if not s:
            continue
        c = cost(ctx["cfg"], tags["state_rows"], tags.get("chunk_tokens", 0))
        scope_s += s
        need_bytes += c["bytes"]
        need_flops += c["flops"]
        n += 1
    if not scope_s:
        return None
    pk = peaks.peaks_of(ctx["device"]["kind"])
    t_mem = need_bytes / pk["hbm_bytes_per_s"]
    t_flop = need_flops / pk["bf16_flops_per_s"]
    print(f"[layer] ssm_state_roofline: bound by "
          f"{'memory' if t_mem >= t_flop else 'compute'}; need {need_bytes:.3e} B, "
          f"{need_flops:.3e} FLOP, scope {scope_s:.6f} s over {n} dispatches",
          file=sys.stderr)
    return 100.0 * max(t_mem, t_flop) / scope_s
