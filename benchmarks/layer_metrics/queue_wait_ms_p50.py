"""Admission (``engine/sched_admission.py``): the median of what the
``queue_wait_seconds`` histogram gained over the window."""

from ._common import histogram_delta_quantile


def read(ctx):
    q = histogram_delta_quantile(ctx, "queue_wait_seconds", 0.5)
    return None if q is None else 1e3 * q
