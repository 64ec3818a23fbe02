"""Admission: share of the prompt tokens the generator sent that were not
prefilled, i.e. 1 - (gain of ``scheduler.prefill_tokens``, the prompt
tokens really prefilled) / (prompt tokens of the window's requests).
``prefix.hits`` counts look-ups, not tokens; it is printed beside it."""

import sys

from ._common import counter_delta


def read(ctx):
    sent = sum(r["prompt_tokens"] for r in ctx["records"] if r["t_tok"])
    if not sent:
        return None
    prefilled = counter_delta(ctx, "scheduler.prefill_tokens")
    print(f"[layer] prefix.hits gained {counter_delta(ctx, 'prefix.hits')}, "
          f"prefilled {prefilled} of {sent} prompt tokens", file=sys.stderr)
    return 100.0 * (1.0 - prefilled / sent)
