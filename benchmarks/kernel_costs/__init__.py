"""Operations and bytes a kernel's calls need, computed from shapes: one
function per kernel, one file per kernel, found by the kernel's name.
``names.json`` lists the trace names that were matched to each kernel (the
program gives them no stable ``named_scope`` yet)."""

from __future__ import annotations

import importlib
import json
import os

with open(os.path.join(os.path.dirname(__file__), "names.json"), encoding="utf-8") as _f:
    NAMES = json.load(_f)


def kernel_of(trace_name: str):
    """The kernel a trace operation belongs to, or None: its name without
    the numbering is one of the kernel's listed names."""
    stem, _, tail = trace_name.rpartition(".")
    base = stem if stem and tail.isdigit() else trace_name
    for kernel, names in NAMES["kernels"].items():
        if base in names:
            return kernel
    return None


def is_attention_kernel(trace_name: str) -> bool:
    return kernel_of(trace_name) in NAMES["attention"]


def cost_fn(kernel: str):
    return importlib.import_module(f"{__name__}.{kernel}").cost


def decode_attention_cost(cfg: dict, contexts, n_steps: int,
                          first_step: int = 0) -> dict:
    """What ``n_steps`` decode steps (the scan's steps ``first_step`` on)
    over sequences of ``contexts`` tokens need of the decode-path
    attention kernel, all layers."""
    return cost_fn(NAMES["decode_kernel"])(cfg, contexts, n_steps, first_step)
