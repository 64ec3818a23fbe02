"""Operations and bytes a kernel's calls need, computed from shapes: one
function per kernel, one file per kernel, found by the kernel's name.

The names files of this directory (``names.json`` first, then every other
``names*.json`` in the order of their names) list the trace names that
belong to each kernel, which kernels are attention, and which jitted
programs are model steps. They are merged: a new kernel, a new member of
``attention`` or a new step program comes as a new file. A list gains the
new file's entries; a single value (``decode_kernel``) stands as the first
file that gives it has it, and a file that gives another is an error.
"""

from __future__ import annotations

import importlib
import json
import os


def load_names(directory: str) -> dict:
    """The merged table of the names files in ``directory``."""
    files = sorted((n for n in os.listdir(directory)
                    if n.startswith("names") and n.endswith(".json")),
                   key=lambda n: (n != "names.json", n))  # names.json first
    merged: dict = {}

    def extend(into: list, more: list) -> None:
        into.extend(x for x in more if x not in into)

    for n in files:
        with open(os.path.join(directory, n), encoding="utf-8") as f:
            for key, value in json.load(f).items():
                if key == "kernels":
                    for kernel, names in value.items():
                        extend(merged.setdefault(key, {}).setdefault(kernel, []), names)
                elif isinstance(value, list):
                    extend(merged.setdefault(key, []), value)
                elif key == "seen_in":
                    continue  # each file's own note of where its names were read
                elif merged.setdefault(key, value) != value:
                    raise ValueError(f"{n} gives {key} = {value!r}; it is "
                                     f"{merged[key]!r} already: an edit, not an addition")
    return merged


NAMES = load_names(os.path.dirname(__file__))


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
