#!/usr/bin/env python3
"""Load generator and SSE clients: a process that never imports JAX.

    python benchmarks/loadgen.py --port P --traffic benchmarks/traffic/x.json \
        --vocab V --seed N --seconds S --out records.jsonl

The server's scheduler loop and these clients' clocks must not share one
interpreter lock, so the harness starts this file as a child. It builds
the whole schedule from the seed before the window opens, offers it for
``--seconds`` seconds on its own clock (``time.perf_counter``, which is
system-wide on Linux, so the parent's spans share it), stops offering at
the window's end, lets requests in flight run to their deadline and no
further, and writes one JSON line per request.

A traffic mix is a data file: ``{"kind": ..., ...parameters}``. ``kind``
names one of the generators below; nothing else in the harness knows a
mix by name.

- ``poisson_open``   independent users: arrivals on a schedule whether or
                     not earlier requests have finished; unique prompts.
- ``sessions_closed`` agents: each session sends its next turn when the
                     reply to the last has arrived, re-sending the whole
                     conversation, all sessions sharing one system prompt.

The schedule belongs to the mix, the content to the seed. Sizes and gaps
are the fixed quantiles of the mix's distributions, in a balanced order
drawn from the mix's own ``schedule_seed``; ``--seed`` draws every token id
(and the model's weights). So every seed offers the same work at the same
instants, and two runs differ in content only: in a closed loop of some
thirty requests a window, or an open loop just under its knee, another
order of the same sizes moved the medians by a quarter (PERF.md, Findings).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import socket
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import tokenizer as tk  # noqa: E402

PATH = "/v1/chat/completions"
GRACE_S = 2.0  # client patience beyond the server's own deadline


# -- sizes -------------------------------------------------------------------


def _norm_ppf(p: float) -> float:
    return statistics.NormalDist().inv_cdf(p)


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: int, hi: int) -> list[int]:
    """The n mid-quantiles of a log-normal, clipped to [lo, hi]."""
    out = []
    for k in range(n):
        v = median * math.exp(sigma * _norm_ppf((k + 0.5) / n))
        out.append(int(min(hi, max(lo, round(v)))))
    return out


def exponential_quantiles(n: int, mean: float) -> list[float]:
    return [-mean * math.log(1.0 - (k + 0.5) / n) for k in range(n)]


def _sizes(spec: dict, n: int) -> list[int]:
    return lognormal_quantiles(n, spec["median"], spec["sigma"],
                               spec["min"], spec["max"])


def _content(rng: random.Random, n: int, vocab: int) -> list[int]:
    return [rng.randrange(tk.FIRST_CONTENT_ID, vocab) for _ in range(n)]


def _body(turns, max_tokens: int, traffic: dict) -> dict:
    return {
        "messages": [{"role": r, "content": tk.text_of(c)} for r, c in turns],
        "max_tokens": int(max_tokens), "stream": True, "temperature": 0,
        "ignore_eos": True, "deadline_s": float(traffic["deadline_s"]),
    }


# -- schedules (a function of the seed alone) --------------------------------


def balanced_order(values: list, rng: random.Random, groups: int = 4) -> list:
    """``values`` in an order drawn from ``rng`` in which every run of
    ``groups`` consecutive items holds one from each quantile group: the
    marginal distribution is untouched, and no stretch of a window is all
    small or all large, whatever the seed."""
    vs = sorted(values)
    per = max(1, len(vs) // groups)
    pools = [vs[i * per:(i + 1) * per] for i in range(groups - 1)]
    pools.append(vs[(groups - 1) * per:])
    for pool in pools:
        rng.shuffle(pool)
    out = []
    while any(pools):
        block = [pool.pop() for pool in pools if pool]
        rng.shuffle(block)
        out.extend(block)
    return out


def _schedule_rng(traffic: dict, stream: int) -> random.Random:
    return random.Random(int(traffic.get("schedule_seed", 0)) * 1000003 + stream)


def poisson_schedule(traffic: dict, seed: int, seconds: float,
                     vocab: int) -> list[dict]:
    rng = random.Random(seed)  # content
    order = _schedule_rng(traffic, 0)  # sizes and instants
    n = max(1, round(traffic["rate_per_s"] * seconds))
    gaps = balanced_order(
        exponential_quantiles(n, 1.0 / traffic["rate_per_s"]), order)
    prompts = balanced_order(_sizes(traffic["prompt_tokens"], n), order)
    outs = balanced_order(_sizes(traffic["max_tokens"], n), order)
    plan, t = [], 0.0
    for i in range(n):
        t += gaps[i]
        if t >= seconds:
            break
        n_content = max(1, prompts[i] - tk.template_overhead(1))
        plan.append({
            "idx": i, "due": t, "max_tokens": outs[i],
            "turns": [("user", _content(rng, n_content, vocab))],
        })
    return plan


def shared_system(traffic: dict, seed: int, vocab: int) -> list[int]:
    return _content(random.Random(seed),
                    traffic["system_prompt_tokens"] - 3, vocab)  # bos, role, eot


def session_plan(traffic: dict, seed: int, vocab: int, p: int) -> dict:
    """Script number ``p``: a task, then per turn max_tokens, the tool
    result that follows the reply, and a stand-in reply of that length
    (the history of a session that is under way when the window opens).
    Every script holds the same quantile set of turn sizes, in its own
    balanced order, which is the script's and not the seed's."""
    rng = random.Random((seed32_(seed) * 1000003 + p + 1) & 0xFFFFFFFFFFFF)
    order = _schedule_rng(traffic, 1 + p)
    turns = int(traffic["turns_per_session"])
    tasks = _sizes(traffic["task_tokens"], 4)
    outs = balanced_order(_sizes(traffic["max_tokens"], turns), order)
    tools = balanced_order(_sizes(traffic["tool_result_tokens"], turns), order)
    # the tool's own running time before its result comes back: the mid-
    # quantiles of a uniform range; 0 where the mix states none
    lo, hi = (traffic.get("think_s") or {"min": 0.0, "max": 0.0}).values()
    thinks = balanced_order(
        [lo + (hi - lo) * (k + 0.5) / turns for k in range(turns)], order)
    return {
        "task": _content(rng, tasks[p % 4], vocab),
        "turns": [
            {"max_tokens": outs[t], "tool": _content(rng, tools[t], vocab),
             "stand_in": _content(rng, outs[t], vocab), "think": thinks[t]}
            for t in range(turns)
        ],
    }


def seed32_(seed: int) -> int:
    return (int(seed) ^ (int(seed) >> 32)) & 0xFFFFFFFF


def start_turn(traffic: dict, slot: int) -> int:
    """The turn a session is at when the window opens: the sessions of a
    live server are spread over a conversation's life, so the window sees
    every context length from its first second."""
    return (slot * int(traffic["turns_per_session"])) // int(traffic["sessions"])


def conversation(system, plan: dict, upto: int) -> list:
    """The turns sent at turn ``upto`` of a script whose earlier replies
    are the stand-ins."""
    turns = [("system", system), ("user", plan["task"])]
    for step in plan["turns"][:upto]:
        turns += [("assistant", step["stand_in"]), ("user", step["tool"])]
    return turns


def session_offsets(traffic: dict, seed: int) -> list[float]:
    rng = _schedule_rng(traffic, 0)
    return [rng.uniform(0.0, traffic["start_spread_s"])
            for _ in range(int(traffic["sessions"]))]


def short_prompt_lengths(traffic: dict, chunk: int, step: int = 16) -> list[int]:
    """Prompt lengths at or under one admission chunk that the mix can
    send, on a grid fine enough to reach every size class of a bucketed
    admission (powers of two, pages of 64): the warm-up sends one each."""
    if traffic["kind"] != "poisson_open":
        return []  # every session turn rides the shared system prompt
    lo = traffic["prompt_tokens"]["min"]
    hi = min(traffic["prompt_tokens"]["max"], chunk)
    if lo > hi:
        return []
    grid = {lo, hi} | {n for n in range(step, hi + 1, step) if n >= lo}
    return sorted(grid)


def prime_bodies(traffic: dict, seed: int, vocab: int) -> list[dict]:
    """What a live server already holds when the window opens: for the
    session mix, the shared system prompt in the prefix cache, and the
    history of each session that is under way (everything before the tool
    result its first turn of the window brings)."""
    if traffic["kind"] != "sessions_closed":
        return []
    no_deadline = {"deadline_s": 0.0}  # set-up: a cold prime waits for compiles
    system = shared_system(traffic, seed, vocab)
    bodies = [_body([("system", system), ("user", system[:8])], 2, no_deadline)]
    for slot in range(int(traffic["sessions"])):
        upto = start_turn(traffic, slot)
        if upto:
            plan = session_plan(traffic, seed, vocab, slot)
            bodies.append(_body(conversation(system, plan, upto)[:-1], 1, no_deadline))
    return bodies


# -- one streamed request ----------------------------------------------------


def stream_request(port: int, body: dict, give_up_at: float,
                   on_first_token=None) -> dict:
    """POST one streamed chat completion; returns arrival times (perf
    counter) and ids of its tokens and a status: ``ok`` or why not.
    ``on_first_token`` is called once, when the first token arrives."""
    rec = {"t_tok": [], "ids": [], "status": "ok", "t_sent": None,
           "t_end": None, "frames": 0}
    data = json.dumps(body).encode()
    head = (f"POST {PATH} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
            f"Content-Type: application/json\r\nContent-Length: {len(data)}\r\n"
            "Connection: close\r\n\r\n").encode()
    sock = fp = None
    try:
        remaining = give_up_at - time.perf_counter()
        if remaining <= 0:
            raise TimeoutError
        sock = socket.create_connection(("127.0.0.1", port), timeout=remaining)
        rec["t_sent"] = time.perf_counter()
        rec["t_sent_wall"] = time.time()
        sock.sendall(head + data)
        fp = sock.makefile("rb")
        in_body = False
        while True:
            remaining = give_up_at - time.perf_counter()
            if remaining <= 0:
                raise TimeoutError
            sock.settimeout(remaining)
            line = fp.readline()
            if not line:
                rec["status"] = "closed_early"
                break
            if not in_body:
                if line.startswith(b"HTTP/"):
                    code = int(line.split()[1])
                    if code != 200:
                        rec["status"] = f"http_{code}"
                        break
                elif line in (b"\r\n", b"\n"):
                    in_body = True
                continue
            if not line.startswith(b"data: "):
                continue
            now = time.perf_counter()
            payload = line[6:].strip()
            if payload == b"[DONE]":
                break
            msg = json.loads(payload)
            if "error" in msg:
                rec["status"] = "error:" + str(msg["error"].get("type"))
                continue
            toks = (msg.get("fei") or {}).get("toks") or []
            if toks:
                if on_first_token is not None and not rec["ids"]:
                    on_first_token()
                rec["frames"] += 1
                rec["ids"].extend(int(t) for t in toks)
                rec["t_tok"].extend([now] * len(toks))
    except (TimeoutError, OSError) as exc:
        rec["status"] = "timeout" if isinstance(exc, TimeoutError) \
            else f"socket:{type(exc).__name__}"
    finally:
        rec["t_end"] = time.perf_counter()
        # closing an unfinished stream cancels it on the server's side
        for closable in (fp, sock):
            if closable is not None:
                try:
                    closable.close()
                except OSError:
                    pass
    if rec["status"] == "ok" and len(rec["ids"]) != body["max_tokens"]:
        rec["status"] = "short"
    return rec


# -- drivers ------------------------------------------------------------------


class Recorder:
    def __init__(self):
        self._lock = threading.Lock()
        self.records: list[dict] = []

    def add(self, rec: dict) -> None:
        with self._lock:
            self.records.append(rec)


def _finish(rec: dict, meta: dict, t0: float) -> dict:
    """Times relative to the window's start; token ids kept for the
    comparison, prompt as (role, ids) turns."""
    rel = lambda t: None if t is None else round(t - t0, 6)  # noqa: E731
    return {
        **meta, "sent": rel(rec["t_sent"]), "end": rel(rec["t_end"]),
        "sent_wall": rec.get("t_sent_wall"),
        "t_tok": [round(t - t0, 6) for t in rec["t_tok"]],
        "ids": rec["ids"], "status": rec["status"], "frames": rec["frames"],
    }


def run_open(port, traffic, seed, seconds, vocab, t0, out: Recorder) -> None:
    plan = poisson_schedule(traffic, seed, seconds, vocab)
    threads = []

    def one(item):
        due = t0 + item["due"]
        body = _body(item["turns"], item["max_tokens"], traffic)
        rec = stream_request(
            port, body, due + traffic["deadline_s"] + GRACE_S)
        out.add(_finish(rec, {
            "idx": item["idx"], "session": None, "turn": 0,
            "due": round(item["due"], 6), "max_tokens": item["max_tokens"],
            "prompt_tokens": sum(len(c) for _, c in item["turns"])
            + tk.template_overhead(len(item["turns"])),
            "turns": [[r, c] for r, c in item["turns"]],
        }, t0))

    for item in plan:
        wait = t0 + item["due"] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=one, args=(item,), daemon=True)
        th.start()
        threads.append(th)
    _join_all(threads, t0 + seconds + traffic["deadline_s"] + 2 * GRACE_S)


def run_closed(port, traffic, seed, seconds, vocab, t0, out: Recorder) -> None:
    n_sessions = int(traffic["sessions"])
    system = shared_system(traffic, seed, vocab)
    offsets = session_offsets(traffic, seed)
    lock = threading.Lock()
    next_plan = iter(range(n_sessions, 10 ** 9))  # after each slot's first
    counter = iter(range(10 ** 9))
    t_stop = t0 + seconds
    limit = int(traffic["max_prompt_tokens"])

    def session(slot: int):
        wait = t0 + offsets[slot] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        p, first = slot, start_turn(traffic, slot)
        while time.perf_counter() < t_stop:
            plan = session_plan(traffic, seed, vocab, p)
            turns = conversation(system, plan, first)
            for t in range(first, len(plan["turns"])):
                step = plan["turns"][t]
                n_prompt = sum(len(c) for _, c in turns) \
                    + tk.template_overhead(len(turns))
                if n_prompt > limit:
                    break
                due = time.perf_counter()
                if due >= t_stop:
                    return
                body = _body(turns, step["max_tokens"], traffic)
                rec = stream_request(
                    port, body, due + traffic["deadline_s"] + GRACE_S)
                with lock:
                    idx = next(counter)
                out.add(_finish(rec, {
                    "idx": idx, "session": p, "turn": t,
                    "due": round(due - t0, 6),
                    "max_tokens": step["max_tokens"],
                    "prompt_tokens": n_prompt,
                    # the shared system prompt is written once, in the header
                    "turns": [[r, c] for r, c in turns[1:]],
                    "shared_system": True,
                }, t0))
                if rec["status"] != "ok":
                    break  # a failed turn ends its session
                turns = turns + [("assistant", rec["ids"]),
                                 ("user", step["tool"])]
                time.sleep(step["think"])  # the tool runs; then the turn is due
            with lock:
                p, first = next(next_plan), 0

    threads = [threading.Thread(target=session, args=(s,), daemon=True)
               for s in range(n_sessions)]
    for th in threads:
        th.start()
    _join_all(threads, t_stop + traffic["deadline_s"] + 2 * GRACE_S)
    out.system = system


def _join_all(threads, until: float) -> None:
    for th in threads:
        th.join(timeout=max(0.0, until - time.perf_counter()))


GENERATORS = {"poisson_open": run_open, "sessions_closed": run_closed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    with open(a.traffic, encoding="utf-8") as f:
        traffic = json.load(f)
    run = GENERATORS[traffic["kind"]]
    out = Recorder()
    t0 = time.perf_counter() + 0.05
    # the parent reads this line to know when the window opened
    print(json.dumps({"window_start": t0, "wall": time.time()}), flush=True)
    run(a.port, traffic, a.seed, a.seconds, a.vocab, t0, out)
    late = sorted(r["sent"] - r["due"] for r in out.records
                  if r["sent"] is not None)
    header = {
        "kind": "header", "window_start": t0, "seconds": a.seconds,
        "seed": a.seed, "traffic": traffic, "requests": len(out.records),
        "system": getattr(out, "system", None),
        "late_ms_p50": 1e3 * late[len(late) // 2] if late else None,
        "late_ms_max": 1e3 * late[-1] if late else None,
        "still_running": sum(1 for t in threading.enumerate()
                             if t is not threading.main_thread()),
    }
    tmp = a.out + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write(json.dumps(header) + "\n")
        for r in sorted(out.records, key=lambda r: r["idx"]):
            f.write(json.dumps(r) + "\n")
    os.replace(tmp, a.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
