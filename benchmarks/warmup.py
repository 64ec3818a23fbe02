"""Scripted warm-up: every program the scheduler can reach under load.

The paged scheduler compiles one program per (scan depth, merged chunk or
not, final chunk or not). Depths are the powers of two up to its
multi-step cap: a batch whose deepest remaining budget is r runs the next
power of two >= r, so stream tails walk down the ladder, and an admission
chunk that is pending rides whatever depth is running. Open traffic hits
all of these sooner or later; a window must hit none for the first time.
So set-up sends, through the served path itself, short scripted pairs: a
decoding request A whose budget ends its ladder on a chosen depth, and a
request B whose prompt of k chunks arrives while A decodes, so that B's
chunks (the last one ``final``) ride A's dispatches. B is sent when A's
first token arrives, that is while A's first scan runs, which puts B's
first chunk on A's second scan. What the scripts reached is printed
(``[warmup] ... programs compiled``), and a program they missed fails the
run at its first use inside a window rather than slowing it unseen.

Prompts are unique (a fixed generator), so the prefix cache shortens no
chunk. Only the request path is used: nothing of the scheduler's insides.
"""

from __future__ import annotations

import random
import threading
import time

from benchmarks import loadgen, tokenizer as tk


def _prompt(rng, n_tokens: int, vocab: int):
    n = max(1, n_tokens - tk.template_overhead(1))
    return [("user", loadgen._content(rng, n, vocab))]


def _budget(scans) -> int:
    """max_tokens whose decode scans have the depths ``scans``: one token
    comes from the prefill, then each scan delivers its depth (the last
    may be cut short, which rounds up to the same depth)."""
    return 1 + sum(scans)


def run(port: int, vocab: int, chunk: int, depth_cap: int, positions: int,
        deadline_s: float, short_prompts=()) -> int:
    """``short_prompts``: prompt lengths (tokens) at or under one chunk
    that the cell's traffic can send; such prompts take the scheduler's
    dense bucketed admission, one program per bucket and page count, and
    each is sent once, alone."""
    rng = random.Random(20260928)
    # no deadline: a cold warm-up request waits for its programs to compile
    traffic = {"deadline_s": 0.0}
    sent = 0

    def send(turns, max_tokens, on_first=None):
        nonlocal sent
        sent += 1
        rec = loadgen.stream_request(
            port, loadgen._body(turns, max_tokens, traffic),
            time.perf_counter() + 600.0, on_first_token=on_first)
        if rec["status"] != "ok":
            raise SystemExit(f"warm-up request failed: {rec['status']}")

    def chunks(k: int):
        return _prompt(rng, min((k - 1) * chunk + max(1, chunk // 4), positions - 64), vocab)

    def pair(a_out: int, b_chunks: int):
        b = threading.Thread(target=send, args=(chunks(b_chunks), 2))
        send(chunks(2), a_out, on_first=b.start)
        b.join()

    cap = depth_cap
    tails = []
    t = cap // 2
    while t >= 1:
        tails.append(t)
        t //= 2
    # alone: both chunk programs, then every scan depth without a chunk
    send(chunks(3), _budget([cap, 1]))
    for t in tails[:-1]:
        send(chunks(2), _budget([cap, t]))
    # a chunk riding the full depth, non-final and final
    pair(_budget([cap] * 5), 3)
    # a chunk riding each tail depth: B's first chunk rides A's second scan
    # (B is sent on A's first token, while the first scan runs), so k = 2
    # puts a final chunk on scan 3 and k = 3 a non-final one
    for t in tails:
        for k in (2, 3):
            pair(_budget([cap, cap, t]), k)
    for n in short_prompts:
        send(_prompt(rng, n, vocab), 2)
    return sent
