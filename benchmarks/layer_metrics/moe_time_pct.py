"""Kernels (``ops/moe.py`` under ``models/deepseek.py``): device seconds of
the operations under the four expert-layer scopes (``moe_route``: scores,
choice, expert order and gather; ``moe_experts``: the grouped products;
``moe_shared``: the shared experts; ``moe_combine``: back to token order,
the weighted sum, the shared part added) over device busy seconds, in the
traced interval. A program without those scopes gives nothing to read."""

from ._scopes import share_of_busy

SCOPES = ("moe_route", "moe_experts", "moe_shared", "moe_combine")


def read(ctx):
    shares = [share_of_busy(ctx, word) for word in SCOPES]
    if all(s is None for s in shares):
        return None
    return sum(s or 0.0 for s in shares)
