#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, and the events that took
most time on each line.  python3 benchmarks/tools/trace_dump.py <trace dir> [out.json]"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import trace_reduce  # noqa: E402


def main() -> int:
    from jax.profiler import ProfileData

    path = trace_reduce.find_xplane(sys.argv[1])
    pd = ProfileData.from_file(path)
    out = {"file": path, "bytes": os.path.getsize(path), "planes": []}
    for plane in pd.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            totals, n, first = {}, 0, None
            for ev in line.events:
                n += 1
                if first is None:
                    first = ev.start_ns
                t = totals.setdefault(ev.name, [0, 0.0])
                t[0] += 1
                t[1] += ev.duration_ns * 1e-9
            top = sorted(totals.items(), key=lambda kv: -kv[1][1])[:40]
            p["lines"].append({"name": line.name, "events": n, "first_ns": first,
                               "top": [[k, v[0], v[1]] for k, v in top]})
        out["planes"].append(p)
    text = json.dumps(out, indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w", encoding="utf-8") as f:
            f.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
