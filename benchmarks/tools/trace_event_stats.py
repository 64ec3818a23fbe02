#!/usr/bin/env python3
"""Look at single events of one trace by hand: what the profiler recorded
about an operation beyond its name, start and duration.

    python3 benchmarks/tools/trace_event_stats.py <trace dir> [name ...]

For each name given (default: the attention kernels, a whole-pool ``copy``,
``copy-done`` and a pool slice/update fusion) it prints the first ``XLA
Ops`` event whose operation name starts with it, with every stat of the
event AND of the event's metadata. ``jax.profiler.ProfileData`` (what
``trace_reduce.py`` reads with) shows only the former; the ``named_scope``
path of an operation sits in the latter (``tf_op``), so this tool reads the
file as the ``XSpace`` protocol buffer it is. It then lists the host-plane
events whose name starts with ``loop.`` or ``decode_step`` (the program's
``FLIGHT.span`` / ``METRICS.span(jax_trace=True)`` annotations), and counts
device time by innermost scope word, as a by-scope table would.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import trace_reduce  # noqa: E402

DEFAULT = ["ragged_paged_attention", "paged_attention", "copy.", "copy-done",
           "dynamic-slice_bitcast_fusion", "bitcast_dynamic-update-slice_fusion",
           "fusion"]
SCOPES = ["embed", "norm", "attn_qkv", "rope", "kv_write", "kv_read",
          "attention", "attn_out", "mlp", "lm_head", "sample", "grammar_mask",
          "pool_carry"]


def _value(stat, names):
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return names.get(stat.ref_value, stat.ref_value)
    v = getattr(stat, kind) if kind else None
    return v if not isinstance(v, bytes) else f"<{len(v)} bytes>"


def scope_of(op_name: str) -> str:
    """The innermost word of the scope vocabulary on an operation's path."""
    for word in reversed(op_name.split("/")):
        if word in SCOPES:
            return word
    return "(none)" if op_name else "(no tf_op)"


def main() -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    path = trace_reduce.find_xplane(sys.argv[1])
    wanted = sys.argv[2:] or DEFAULT
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {"file": path, "events": {}, "host_annotations": {}, "by_scope_s": {}}
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                for ev in line.events:
                    meta = plane.event_metadata[ev.metadata_id]
                    short = trace_reduce.short_name(meta.name)
                    mstats = {names.get(s.metadata_id): _value(s, names)
                              for s in meta.stats}
                    scope = scope_of(str(mstats.get("tf_op") or ""))
                    out["by_scope_s"][scope] = out["by_scope_s"].get(scope, 0.0) \
                        + ev.duration_ps * 1e-12
                    for w in wanted:
                        if short.startswith(w) and w not in out["events"]:
                            out["events"][w] = {
                                "name": short, "duration_s": ev.duration_ps * 1e-12,
                                "event_stats": {names.get(s.metadata_id): _value(s, names)
                                                for s in ev.stats},
                                "metadata_stats": mstats,
                                "metadata_display_name": meta.display_name,
                            }
            break  # the first chip
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                if name.startswith(("loop.", "decode_step", "prefill")):
                    rec = out["host_annotations"].setdefault(
                        name, {"plane": plane.name, "line": line.name, "events": 0,
                               "seconds": 0.0})
                    rec["events"] += 1
                    rec["seconds"] += ev.duration_ps * 1e-12
    out["by_scope_s"] = dict(sorted(out["by_scope_s"].items(), key=lambda kv: -kv[1]))
    print(json.dumps(out, indent=1, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
