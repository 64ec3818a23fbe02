"""Admission (``engine/sched_admission.py``): ``admitted`` ->
``first_token``, the dispatches that put the prompt's keys and values into
pages and sample the first token. Mean over the requests queued inside the
window. Standard error carries the sum of the three parts of the time to
first token beside the client's own mean and what is left between them
(the socket, and requests the two sides count differently)."""

import sys

from benchmarks import e2e_metrics, loadgen

from . import entry_host_ms_mean, queue_wait_ms_mean
from ._spans import first_tokens, mean_ms


def read(ctx):
    reqs = first_tokens(ctx)
    value = mean_ms(b["first_token"] - b["admitted"] for b in reqs)
    if value is None:
        return None
    parts = [entry_host_ms_mean.read(ctx), queue_wait_ms_mean.read(ctx), value]
    client = e2e_metrics.summarize(
        ctx["records"], ctx["seconds"], ctx["chips"],
        ctx["traffic"]["deadline_s"] + loadgen.GRACE_S).get("ttft_ms_mean")
    if client is not None:
        print(f"[layer] prefill_ms_mean: entry_host {parts[0]:.3f} + queue_wait "
              f"{parts[1]:.3f} + prefill {parts[2]:.3f} = {sum(parts):.3f} ms over "
              f"{len(reqs)} requests; client ttft_ms_mean "
              f"{client:.3f}; remainder {client - sum(parts):.3f} ms",
              file=sys.stderr)
    return value
