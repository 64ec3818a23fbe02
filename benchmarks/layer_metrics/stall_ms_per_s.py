"""Entry (``ui/server.py`` starts ``obs/proc.py``'s watch): milliseconds a
second the process lost to heartbeat wakes more than 50 ms late (the whole process stood still):
gain of ``proc.stall_seconds`` over the window (the ``before`` / ``after``
snapshots) over the window's seconds. Over the whole window, not the traced
interval; the ``after`` snapshot is taken when the window's last stream has
drained, so what falls in the drain counts too (the table's window line
counts the ring's spans inside the window's own seconds). None for a
program without the counter."""

from ._common import counter_delta


def read(ctx):
    if "proc.stall_seconds" not in ctx["after"]["snap"]["counters"]:
        return None
    return 1e3 * counter_delta(ctx, "proc.stall_seconds") / ctx["seconds"]
