"""Kernels (``ops/sparse_select.py`` under ``models/sala.py``): device
seconds of the operations under the ``sparse_select`` scope (scoring the
compressed keys, the block maximum, top-k and the page lists) over device
busy seconds, in the traced interval. A program without that scope gives
nothing to read."""

from ._scopes import share_of_busy


def read(ctx):
    return share_of_busy(ctx, "sparse_select")
