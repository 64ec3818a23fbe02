"""Admission: how many device dispatches a request's first token waits
for. Mean, over the requests queued inside the window, of the number of
``dispatch.step`` and ``dispatch.prefill_chunk`` records whose issue-to-sync
interval overlaps the request's [``queued``, ``first_token``]: the one in
flight when it arrived, then those that carry its chunks. The scheduler
admits only between dispatches, so this count times a dispatch's length is
the time to first token."""

from ._spans import dispatches, first_tokens, overlap


def read(ctx):
    reqs = first_tokens(ctx)
    disp = dispatches(ctx)
    if not reqs or not disp:
        return None
    counts = [sum(1 for d0, d1, _ in disp
                  if overlap(d0, d1, b["queued"], b["first_token"]) > 0)
              for b in reqs]
    return sum(counts) / len(counts)
