"""Kernels (``ops/ssd.py`` under ``models/falcon_h1.py``): device seconds
of the operations under the mixer's five scopes (``ssm_in``: projection,
multipliers, split; ``ssm_conv``; ``ssm_state``: the recurrence with
read-out and skip, the state read and written; ``ssm_gate``: gate and
grouped norm; ``ssm_out``) over device busy seconds, in the traced
interval. The five do not nest, so an operation counts once. A program
without those scopes gives nothing to read."""

import sys

from ._scopes import seconds_under

SCOPES = ("ssm_in", "ssm_conv", "ssm_state", "ssm_gate", "ssm_out")


def read(ctx):
    tr = ctx["trace"]
    if not tr["devices"] or not tr.get("busy_s"):
        return None
    parts = {w: seconds_under(w) or 0.0 for w in SCOPES}
    if not sum(parts.values()):
        return None
    print("[layer] ssm_time_pct: " + ", ".join(
        f"{w} {100.0 * s / tr['busy_s']:.2f}%" for w, s in parts.items()),
        file=sys.stderr)
    return 100.0 * sum(parts.values()) / tr["busy_s"]
