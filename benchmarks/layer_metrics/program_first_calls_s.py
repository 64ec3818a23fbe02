"""Model step (``obs/flight.CompileObserver``): seconds the program's own
jitted programs spent in their first calls before the window (a cache load
or a compile): ``spans.compile.total_s`` of the ``before`` snapshot.
Standard error lists every ``compile`` event still in the ring, longest
first."""

import sys


def read(ctx):
    total = ctx["before"]["snap"].get("spans", {}).get("compile")
    if total is None:
        return None
    events = sorted((r["tags"] for r in ctx["flight"] if r["name"] == "compile"),
                    key=lambda t: -t.get("seconds", 0.0))
    print(f"[layer] program_first_calls_s: {total['count']} first calls, "
          f"{total['total_s']:.3f} s; in the ring: " + ", ".join(
              f"{t.get('family')}{t.get('key')} {t.get('seconds', 0.0):.3f}"
              for t in events), file=sys.stderr)
    return total["total_s"]
