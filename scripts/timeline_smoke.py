#!/usr/bin/env python
"""CI timeline smoke: flight recorder + /debug/timeline end-to-end.

Drives a short mixed workload on a tiny paged engine — concurrent
admissions, turbo multi-step decode, and at least one KV-pressure
preemption (the tight pool from tests/test_preemption.py makes two
worst-case reservations collide organically) — then fetches
``GET /debug/timeline`` through the socket-free ServeAPI core and
validates the Chrome-trace JSON:

- parses as JSON with a non-empty ``traceEvents`` list;
- every dispatch is an ``<name>.issue`` / ``<name>.sync`` complete-event
  pair (equal counts, µs timestamps, non-negative durations);
- dispatch spans carry the request trace id(s) and the serving-mesh tag;
- the preempt instant made it onto the timeline;
- ``GET /v1/traces/<id>`` returns the trace plus its flight slice.

Runs on whatever platform JAX comes up on.
Exit status: 0 clean, non-zero with a reason on stderr.
"""

from __future__ import annotations

import json
import sys
import threading
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def fail(msg: str) -> int:
    print(f"timeline smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def main() -> int:
    import os

    os.environ.setdefault("FEI_TPU_SCHED_MULTISTEP", "4")
    from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
    from fei_tpu.obs import FLIGHT, TRACES
    from fei_tpu.ui.server import ServeAPI

    FLIGHT.reset()

    # the tight-pool geometry from tests/test_preemption.py: page_size=4
    # puts one 18-prompt/24-budget request at 11 pages; 13 allocatable
    # pages cannot hold two, so concurrent streams preempt organically
    engine = InferenceEngine.from_config(
        "tiny", paged=True, batch_size=2, page_size=4, num_pages=14,
        prefix_cache=True,
    )
    sched = engine.scheduler
    gen = GenerationConfig(max_new_tokens=24, temperature=0.0,
                           ignore_eos=True)
    prompts = [list(range(11 + i, 29 + i)) for i in range(4)]
    seqs = [sched.submit(p, gen) for p in prompts]
    results: list = [None] * len(seqs)

    def go(i: int) -> None:
        results[i] = list(sched.drain(seqs[i]))

    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(seqs))]
    [t.start() for t in threads]
    [t.join(timeout=300) for t in threads]
    if not all(r for r in results):
        return fail("a stream produced no tokens or never finished")

    api = ServeAPI(provider=None)
    status, payload = api.handle("GET", "/debug/timeline", {}, {})[:2]
    if status != 200:
        return fail(f"GET /debug/timeline -> {status}")
    # round-trip through JSON: the endpoint's contract is serializability
    trace = json.loads(json.dumps(payload))
    events = trace.get("traceEvents")
    if not events:
        return fail("traceEvents empty")

    issues = [e for e in events if e.get("ph") == "X"
              and e["name"].endswith(".issue")]
    syncs = [e for e in events if e.get("ph") == "X"
             and e["name"].endswith(".sync")]
    if not issues:
        return fail("no dispatch .issue spans on the timeline")
    if len(issues) != len(syncs):
        return fail(f"{len(issues)} .issue spans vs {len(syncs)} .sync")
    for e in issues + syncs:
        if e["dur"] < 0 or e["ts"] <= 0:
            return fail(f"bad span timing: {e}")
        args = e.get("args", {})
        if e["name"].startswith(("dispatch.step", "dispatch.decode",
                                 "dispatch.prefill")):
            if "mesh" not in args:
                return fail(f"dispatch span without mesh tag: {e}")
            if not (args.get("rid") or args.get("rids")):
                return fail(f"dispatch span without request ids: {e}")

    counts = FLIGHT.counts()
    if counts.get("preempt", 0) < 1:
        return fail(f"no preemption on the timeline (counts: {counts})")
    if counts.get("admit", 0) < len(prompts):
        return fail(f"admissions missing (counts: {counts})")

    rid = seqs[0].rid
    status, payload = api.handle("GET", f"/v1/traces/{rid}", {}, {})[:2]
    if status != 200:
        return fail(f"GET /v1/traces/{rid} -> {status}")
    if payload.get("id") != rid or not payload.get("flight"):
        return fail(f"trace fetch missing flight slice for {rid}")
    status, _ = api.handle("GET", "/v1/traces/req-nope", {}, {})[:2]
    if status != 404:
        return fail(f"unknown trace id returned {status}, wanted 404")
    assert TRACES.get(rid) is not None

    print(
        f"timeline smoke: OK — {len(events)} trace events, "
        f"{len(issues)} dispatches, {counts.get('preempt', 0)} preempts, "
        f"{counts.get('admit', 0)} admits"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
