"""Model step (``engine/sched_decode.py``): device idle seconds of the
traced interval that lie under a ``loop.build`` span (page growth, batch
vectors and uploads before a dispatch is issued), over that interval; one
part of ``device_idle_pct`` (``_idle.py`` has the rule and prints the
table)."""

from ._idle import pct_under


def read(ctx):
    return pct_under(ctx, "loop.build")
