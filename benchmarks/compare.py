"""The comparison that decides ``correct`` for a served model.

Once the window has closed: a sample of the requests it finished, drawn
from the seed, the longest among them; the plain reference run once over
each prompt with its served tokens; and for every served token the gap by
which its reference logit lies below the reference's best at that
position. A sound greedy stream picks the reference's best token or a
near tie, so its widest gap is small; a path computed in a lower precision
than the configuration states, or a token altered where it is produced,
leaves gaps of the order of the logits' spread. The work is bounded
before the run: ``max_requests`` requests, each padded to a multiple of
``pad_to`` positions, ``max_read`` served tokens read from each.
"""

from __future__ import annotations

import random

import numpy as np

from benchmarks import tokenizer as tk
from benchmarks.reference import decoder, seedweights as sw


def full_ids(rec: dict, system) -> tuple[list[int], int]:
    turns = [(r, c) for r, c in rec["turns"]]
    if rec.get("shared_system"):
        turns = [("system", system)] + turns
    prompt = tk.template_ids(turns)
    return prompt + [int(t) for t in rec["ids"]], len(prompt)


def pick(records: list[dict], seed: int, max_requests: int) -> list[dict]:
    """The longest finished request and ``max_requests - 1`` others."""
    ok = [r for r in records if r["status"] == "ok" and r["ids"]]
    if not ok:
        return []
    ok.sort(key=lambda r: r["idx"])
    longest = max(ok, key=lambda r: (r["prompt_tokens"] + len(r["ids"]), -r["idx"]))
    rest = [r for r in ok if r is not longest]
    random.Random(seed).shuffle(rest)
    return [longest] + rest[:max(0, max_requests - 1)]


def gaps(cfg: dict, seed: int, sample: list[dict], system,
         control=False) -> dict:
    """Per-token gaps of the served tokens under the reference; with
    ``control`` (True: the next precision down; or a list of precision
    names) also those of the tokens a lower precision puts first at the
    same positions (teacher-forced, no decoding)."""
    import jax.numpy as jnp

    spec = cfg["compare"]
    precision = cfg["weights"]["precision"]
    ref = decoder.logits_fn(cfg, precision)
    names = [] if not control else (
        [sw.CONTROL_OF[precision]] if control is True else list(control))
    lows = {n: decoder.logits_fn(cfg, n) for n in names}
    s32 = jnp.uint32(sw.seed32(seed))
    served, agree = [], 0
    lowered = {n: [] for n in names}
    for rec in sample:
        ids, n_prompt = full_ids(rec, system)
        n_read = min(len(rec["ids"]), spec["max_read"])
        T = -(-len(ids) // spec["pad_to"]) * spec["pad_to"]
        padded = np.zeros((T,), dtype=np.int32)
        padded[:len(ids)] = ids
        pos = np.zeros((spec["max_read"],), dtype=np.int32)
        pos[:n_read] = np.arange(n_prompt - 1, n_prompt - 1 + n_read)
        logits = np.asarray(ref(s32, jnp.asarray(padded), jnp.asarray(pos)))[:n_read]
        best = logits.max(axis=-1)
        tokens = np.asarray(ids[n_prompt:n_prompt + n_read])
        served.extend((best - logits[np.arange(n_read), tokens]).tolist())
        agree += int((logits.argmax(axis=-1) == tokens).sum())
        for n, low in lows.items():
            lo = np.asarray(low(s32, jnp.asarray(padded), jnp.asarray(pos)))[:n_read]
            lowered[n].extend(
                (best - logits[np.arange(n_read), lo.argmax(axis=-1)]).tolist())
    out = {"served": served, "top1_agree": agree}
    if names:
        out["control"] = lowered[names[0]]
        out["controls"] = lowered
    return out


def verdict(cfg: dict, g: dict, n_requests: int) -> tuple[bool, dict]:
    """(correct, checks): each number compared beside its limit."""
    limits = cfg["compare"]["limits"]
    served = g["served"]
    checks = {
        "compared_tokens": {"value": len(served),
                            "limit": cfg["compare"]["min_tokens"]},
        "logit_gap_max": {"value": max(served) if served else None,
                          "limit": limits["logit_gap_max"]},
        "logit_gap_mean": {"value": sum(served) / len(served) if served else None,
                           "limit": limits["logit_gap_mean"]},
    }
    ok = len(served) >= checks["compared_tokens"]["limit"] and all(
        checks[k]["value"] is not None and checks[k]["value"] <= checks[k]["limit"]
        for k in ("logit_gap_max", "logit_gap_mean")
    )
    checks["compared_requests"] = {"value": n_requests, "limit": None}
    checks["top1_agree"] = {"value": g["top1_agree"], "limit": None}
    for n, c in g.get("controls", {}).items():
        checks[f"control_{n}_gap_max"] = {"value": max(c), "limit": None}
        checks[f"control_{n}_gap_mean"] = {"value": sum(c) / len(c), "limit": None}
    return ok, checks
