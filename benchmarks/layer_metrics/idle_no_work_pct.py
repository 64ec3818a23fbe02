"""Device: device idle seconds of the traced interval that lie under a
``loop.idle`` span (the loop had nothing queued, admitting or armed: the
load, not the program, left the device idle), over that interval; one part
of ``device_idle_pct`` (``_idle.py`` has the rule and prints the table)."""

from ._idle import IDLE_SPAN, pct_under


def read(ctx):
    return pct_under(ctx, IDLE_SPAN)
