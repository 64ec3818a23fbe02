"""Pool (``engine/paged_cache.py``): highest ``pool.pages_in_use`` over
``pool.pages_total``, sampled through the window. The gain of
``scheduler.preemptions`` is printed beside it."""

import sys

from ._common import counter_delta


def read(ctx):
    shares = [u / t for _, u, t in ctx["pool_samples"] if t]
    if not shares:
        return None
    print(f"[layer] scheduler.preemptions gained "
          f"{counter_delta(ctx, 'scheduler.preemptions')}", file=sys.stderr)
    return 100.0 * max(shares)
