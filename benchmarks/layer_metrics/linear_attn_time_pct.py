"""Kernels (``ops/linear_attention.py`` under ``models/sala.py``): device
seconds of the operations under the ``linear_attn`` scope (the linear
layers' recurrence: the one-token step in decode, the chunkwise form in an
admission chunk, the state read and written) over device busy seconds, in
the traced interval. A program without that scope gives nothing to read."""

from ._scopes import share_of_busy


def read(ctx):
    return share_of_busy(ctx, "linear_attn")
