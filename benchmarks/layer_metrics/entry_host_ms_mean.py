"""Entry (``ui/server.py``, ``agent/providers.py``): what the server does
outside the scheduler before a request's first token shows, measured where
it happens: ``http_accepted`` -> ``queued`` (parsing, chat template,
tokenizing) plus ``first_token`` -> ``first_frame`` (detokenizing, framing,
the write of the first content frame). Mean over the requests queued inside
the window."""

from ._spans import first_tokens, mean_ms


def read(ctx):
    return mean_ms((b["queued"] - b["http_accepted"])
                   + (b["first_frame"] - b["first_token"])
                   for b in first_tokens(ctx))
