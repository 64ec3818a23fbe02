"""Model step (``engine/scheduler.py``, ``engine/sched_decode.py``): of the
wall time of the loop's phase spans inside the traced interval, the share
the loop's thread was not on a CPU (``dur_s - cpu_s``, summed before it is
subtracted: waiting for the interpreter while handler threads hold it, or
blocked). Over the spans that hold no dispatch: one that does (a solo
admission chunk inside ``loop.admit``) carries the launch's wait in its
``cpu_s`` gap and is left out (``_idle.phase_cpu`` says why, the table how
many). None where the spans carry no ``cpu_s``."""

from ._idle import phase_cpu


def read(ctx):
    phases = phase_cpu(ctx)
    if phases is None:
        return None
    wall = sum(v[0] for v in phases.values())
    return (100.0 * (wall - sum(v[1] for v in phases.values())) / wall
            if wall else None)
