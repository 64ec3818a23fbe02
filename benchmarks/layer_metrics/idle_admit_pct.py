"""Admission (``engine/scheduler.py``, ``engine/sched_admission.py``):
device idle seconds of the traced interval that lie under a ``loop.admit``
span (queue to slot; an admission's own dispatch lies inside the span, so
what is idle there is its host work, launch and fetch), over that interval;
one part of ``device_idle_pct`` (``_idle.py`` has the rule and prints the
table)."""

from ._idle import pct_under


def read(ctx):
    return pct_under(ctx, "loop.admit")
