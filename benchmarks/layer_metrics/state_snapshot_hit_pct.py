"""Admission (``engine/paged_cache.PrefixCache`` with a recurrent state):
share of the prompt tokens the generator sent in the window that were not
recomputed because their admission resumed from a snapshot of the linear
layers' state: gain of ``state.resumed_tokens`` over prompt tokens sent. A
program without that counter gives nothing to read."""

import sys

from ._common import counter_delta


def read(ctx):
    if "state.resumed_tokens" not in ctx["after"]["snap"]["counters"]:
        return None
    sent = sum(r["prompt_tokens"] for r in ctx["records"] if r["t_tok"])
    if not sent:
        return None
    resumed = counter_delta(ctx, "state.resumed_tokens")
    print(f"[layer] state.snapshot_hits gained "
          f"{counter_delta(ctx, 'state.snapshot_hits')}, snapshots "
          f"{counter_delta(ctx, 'state.snapshots')}, resumed {resumed} of "
          f"{sent} prompt tokens", file=sys.stderr)
    return 100.0 * resumed / sent
