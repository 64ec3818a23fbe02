"""Admission (``engine/sched_admission.py``): ``queued`` -> ``admitted``,
the wait for the scheduler loop to come round and for a slot and pages.
Mean over the same requests as ``entry_host_ms_mean`` and
``prefill_ms_mean``, so that the three add up to the server's side of the
time to first token."""

from ._spans import first_tokens, mean_ms


def read(ctx):
    return mean_ms(b["admitted"] - b["queued"] for b in first_tokens(ctx))
