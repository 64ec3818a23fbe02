"""Device: device idle seconds of the traced interval that no
``loop.deliver``, ``loop.build``, ``loop.admit`` or ``loop.idle`` span
covers, over that interval: under a dispatch's own issue or sync stretch
(launch and fetch), under ``loop.reap`` / ``loop.ctl``, under no record at
all. With the other four parts it sums to ``device_idle_pct``; the table of
``_idle.py`` on standard error splits it further."""

from ._idle import pct_other


def read(ctx):
    return pct_other(ctx)
