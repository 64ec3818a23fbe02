#!/usr/bin/env python
"""CI fleet smoke: the multi-replica front door end-to-end.

Drives fei_tpu.fleet.Router over TWO in-process tiny replicas (real paged
engines behind socket-free ServeAPI cores) and proves the PR's robustness
claims on CPU, no ports, no subprocesses:

1. mixed-tenant threaded load lands entirely — every request reaches 200
   within a bounded number of client-side backpressure retries (429/503
   are the protocol, not losses);
2. breaker round-trip — a replica-scoped injected connection fault
   (``router.forward``, match r0) trips the circuit breaker, the fleet
   keeps serving through r1, and after the cooldown a half-open health
   probe READMITS r0 (``router.ejections`` and ``router.readmissions``
   both move);
3. zero-downtime rolling restart — drain → warm-restart sequenced across
   both replicas while streaming load keeps flowing; zero streams that
   had tokens flowing die mid-stream, and every request still completes.

A chaos sweep re-runs this file with FEI_TPU_FAULT
sweeping ``router.forward:{conn,http503,hang}`` and ``replica.health:
conn`` — the retry/breaker/force-reprobe paths must absorb each kind
with no assertion weakened (the env-armed counts are below the breaker
threshold times the replica count).

Exit status: 0 clean, non-zero with a reason on stderr.
"""

from __future__ import annotations

import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def fail(msg: str) -> int:
    print(f"fleet smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def kv_main() -> int:
    """KV-tier oversubscription smoke (``FEI_TPU_FLEET_SMOKE_MODE=kv``).

    Two tiny replicas with deliberately tight paged pools and the host
    KV tier on (FEI_TPU_KV_TIER, default ram) serve
    ``replicas × slots × FEI_TPU_FLEET_SMOKE_OVERSUB`` concurrent
    sessions through the router, so the scheduler must constantly park
    and resume. Asserts: every request reaches 200 (no wedge, no loss);
    the pool actually preempted; and — without injected chaos — every
    resume streamed pages back (``kv.pages_restored`` moved,
    ``kv.fetch_fallbacks`` and ``preempted_tokens_recomputed`` did not).
    A chaos sweep re-runs this mode with FEI_TPU_FAULT sweeping
    ``kv.spill``/``kv.fetch`` — under chaos the tier is ALLOWED to fall
    back to token replay, but a failed fetch must still complete every
    request (fallback, never wedge)."""
    import os

    os.environ.setdefault("FEI_TPU_KV_TIER", "ram")
    os.environ.setdefault("FEI_TPU_MAX_QUEUE", "32")

    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.engine.engine import InferenceEngine
    from fei_tpu.fleet import InProcessReplica, Router
    from fei_tpu.ui.server import ServeAPI
    from fei_tpu.utils.metrics import METRICS

    def make_api():
        # 16 pages of 4 ≈ 64 positions: one ~31-token prompt + 16 new
        # tokens fits, two co-resident sequences cannot — co-residency
        # forces the spill-before-preempt rung
        engine = InferenceEngine.from_config(
            "tiny", paged=True, batch_size=2, page_size=4, num_pages=16,
            max_seq_len=256, prefix_cache=True,
        )
        return ServeAPI(JaxLocalProvider(engine=engine), model_name="fleet")

    replicas = [InProcessReplica(f"r{i}", api=make_api()) for i in range(2)]
    router = Router(replicas, retries=2, backoff_s=0.02, health_ttl_s=0.1)

    oversub = max(2, int(os.environ.get("FEI_TPU_FLEET_SMOKE_OVERSUB", "5")))
    n = len(replicas) * 2 * oversub
    c0 = METRICS.snapshot()["counters"]
    outcomes: list = [None] * n

    def worker(i: int) -> None:
        body = {
            "messages": [{"role": "user", "content": f"kv smoke {i:03d}"}],
            "max_tokens": 16, "temperature": 0, "session": f"kv-{i}",
        }
        last = "no attempt"
        for _ in range(80):
            res = router.handle("POST", "/v1/chat/completions", body, {})
            if res[0] == 200:
                outcomes[i] = (True, "ok")
                return
            last = f"{res[0]}: {res[1]}"
            time.sleep(0.05)
        outcomes[i] = (False, last)

    print(f"fleet smoke(kv): {n} sessions over "
          f"{len(replicas)}x2 slots ({oversub}x oversubscription)...")
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    [t.start() for t in threads]
    [t.join(timeout=600) for t in threads]
    bad = [(i, o) for i, o in enumerate(outcomes) if not (o and o[0])]
    if bad:
        return fail(f"kv oversubscription lost/wedged requests: {bad[:3]}")

    c1 = METRICS.snapshot()["counters"]

    def delta(k: str) -> float:
        return c1.get(k, 0) - c0.get(k, 0)

    if delta("scheduler.preemptions") <= 0:
        return fail("pool never preempted — the oversubscription smoke "
                    "proved nothing; tighten num_pages")
    chaos = "kv." in os.environ.get("FEI_TPU_FAULT", "")
    if not chaos:
        if delta("kv.spills") <= 0 or delta("kv.pages_restored") <= 0:
            return fail(
                f"tier never engaged: spills={delta('kv.spills')} "
                f"pages_restored={delta('kv.pages_restored')}"
            )
        if delta("kv.fetch_fallbacks") > 0:
            return fail(f"{delta('kv.fetch_fallbacks'):.0f} resumes fell "
                        "back to replay with no fault armed")
        if delta("scheduler.preempted_tokens_recomputed") > 0:
            return fail(
                "streamed resume missed: "
                f"{delta('scheduler.preempted_tokens_recomputed'):.0f} "
                "token positions were re-prefilled"
            )
    print(
        "fleet smoke(kv): OK — "
        f"{n} requests all 200, "
        f"preemptions={delta('scheduler.preemptions'):.0f} "
        f"spills={delta('kv.spills'):.0f} "
        f"pages_restored={delta('kv.pages_restored'):.0f} "
        f"recomputed={delta('scheduler.preempted_tokens_recomputed'):.0f} "
        f"fallbacks={delta('kv.fetch_fallbacks'):.0f} "
        f"spill_failures={delta('kv.spill_failures'):.0f}"
        + (" [chaos]" if chaos else "")
    )
    for r in replicas:
        eng = r.engine
        if eng is not None:
            eng.close()
    return 0


def kvcdn_main() -> int:
    """KV CDN smoke (``FEI_TPU_FLEET_SMOKE_MODE=kvcdn``).

    Two tiny replicas with the host KV tier on and content-addressed
    prefixes enabled. Phase 1 lands several sessions sharing ONE prompt
    on r0 — the tier must hold exactly one content-addressed copy
    (``kv.cas_stores`` moves once, ``kv.cas_dedup_hits`` absorbs the
    rest). Phase 2 drains r0 and sends COLD sessions with the same
    prompt through the router: they land on r1, the router pulls the
    prefix blob off draining r0 by content hash
    (``kv.prefix_hits_remote``), and r1 admits over fetched bytes
    (``kv.prefix_hits_tier``) instead of re-prefilling. Phase 3 rolls
    the fleet and asserts speculative pre-warm pushed hot prefixes into
    the restarted replicas (``router.prewarm_pushes``). A chaos sweep
    re-runs this mode with FEI_TPU_FAULT sweeping ``kv.fetch`` — under
    chaos every CDN rung is ALLOWED to fall back to plain prefill, but
    every request must still reach 200 (degrade, never wedge)."""
    import os
    import tempfile

    os.environ.setdefault("FEI_TPU_KV_TIER", "ram")
    os.environ.setdefault("FEI_TPU_MAX_QUEUE", "32")

    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.engine.engine import InferenceEngine
    from fei_tpu.fleet import InProcessReplica, Router
    from fei_tpu.ui.server import ServeAPI
    from fei_tpu.utils.metrics import METRICS

    def factory():
        # roomy pool: this smoke is about prefix bytes moving, not
        # preemption churn (kv_main owns that)
        engine = InferenceEngine.from_config(
            "tiny", paged=True, batch_size=2, page_size=4, num_pages=64,
            max_seq_len=256, prefix_cache=True,
        )
        return ServeAPI(JaxLocalProvider(engine=engine), model_name="fleet")

    replicas = [
        InProcessReplica(
            f"r{i}", factory=factory,
            drain_dir=tempfile.mkdtemp(prefix=f"fei-kvcdn-smoke-r{i}-"),
        )
        for i in range(2)
    ]
    router = Router(replicas, retries=2, backoff_s=0.02, health_ttl_s=0.1)
    chaos = "kv." in os.environ.get("FEI_TPU_FAULT", "")
    c0 = METRICS.snapshot()["counters"]

    def delta(k: str) -> float:
        return METRICS.snapshot()["counters"].get(k, 0) - c0.get(k, 0)

    # every session shares this prompt: the content hash is the same
    # fleet-wide, which is the entire point of the CDN
    shared = ("Summarize the shared repository context: module layout, "
              "paging design, and the scheduler admission flow.")

    def body(i: int) -> dict:
        return {
            "messages": [{"role": "user", "content": shared}],
            "max_tokens": 8, "temperature": 0, "session": f"cdn-{i}",
        }

    def send(via, i: int) -> tuple[bool, str]:
        last = "no attempt"
        for _ in range(80):
            if via is router:
                res = router.handle("POST", "/v1/chat/completions",
                                    body(i), {})
            else:
                res = via.request("POST", "/v1/chat/completions",
                                  body(i), {})
            if res[0] == 200:
                return True, "ok"
            last = f"{res[0]}: {res[1]}"
            time.sleep(0.05)
        return False, last

    # --- 1. one prompt, many sessions, ONE tier copy on r0 -----------------
    n_warm = 6
    for i in range(n_warm):
        ok, why = send(replicas[0], i)
        if not ok:
            return fail(f"warm session {i} never landed on r0: {why}")
    if not chaos:
        if delta("kv.cas_stores") < 1:
            return fail("no content-addressed blob was ever published "
                        f"(cas_stores={delta('kv.cas_stores'):.0f})")
        if delta("kv.cas_dedup_hits") < 1:
            return fail(
                f"{n_warm} identical sessions produced no dedup hit "
                f"(dedup_hits={delta('kv.cas_dedup_hits'):.0f})"
            )
    print(f"fleet smoke(kvcdn): warm ok — {n_warm} sessions, "
          f"cas_stores={delta('kv.cas_stores'):.0f} "
          f"dedup_hits={delta('kv.cas_dedup_hits'):.0f}")

    # --- 2. drain r0; cold sessions on r1 fetch the prefix by hash ---------
    try:
        replicas[0].request("POST", "/drain", {})
    except Exception as exc:  # noqa: BLE001
        return fail(f"drain of r0 failed: {exc!r}")
    for i in range(n_warm, n_warm + 3):
        ok, why = send(router, i)
        if not ok:
            return fail(f"cold session {i} lost during r0 drain: {why}")
    if not chaos:
        if delta("kv.prefix_hits_remote") < 1:
            return fail(
                "router never fetched the prefix off draining r0 "
                f"(remote_hits={delta('kv.prefix_hits_remote'):.0f} "
                f"fetch_failures={delta('router.prefix_fetch_failures'):.0f})"
            )
        if delta("kv.prefix_hits_tier") < 1:
            return fail(
                "r1 never admitted over fetched bytes "
                f"(tier_hits={delta('kv.prefix_hits_tier'):.0f})"
            )
    print(f"fleet smoke(kvcdn): fetch ok — "
          f"remote_hits={delta('kv.prefix_hits_remote'):.0f} "
          f"tier_hits={delta('kv.prefix_hits_tier'):.0f} "
          f"tokens_saved={delta('kv.prefix_tokens_saved'):.0f}")

    # --- 3. rolling restart pre-warms the fresh replicas -------------------
    report = router.rolling_restart(drain_deadline_s=60.0, wait_s=120.0)
    if not all(v.get("healthy") for v in report.values()):
        return fail(f"a replica did not come back healthy: {report}")
    if not chaos and delta("router.prewarm_pushes") < 1:
        return fail(
            "rolling restart never pre-warmed a fresh replica "
            f"(pushes={delta('router.prewarm_pushes'):.0f} "
            f"failures={delta('router.prewarm_failures'):.0f})"
        )
    ok, why = send(router, n_warm + 3)
    if not ok:
        return fail(f"post-restart session lost: {why}")
    print(
        "fleet smoke(kvcdn): OK — "
        f"cas_stores={delta('kv.cas_stores'):.0f} "
        f"dedup_hits={delta('kv.cas_dedup_hits'):.0f} "
        f"remote_hits={delta('kv.prefix_hits_remote'):.0f} "
        f"tier_hits={delta('kv.prefix_hits_tier'):.0f} "
        f"prewarm_pushes={delta('router.prewarm_pushes'):.0f} "
        f"fetch_fallbacks={delta('kv.fetch_fallbacks'):.0f}"
        + (" [chaos]" if chaos else "")
    )
    for r in replicas:
        eng = r.engine
        if eng is not None:
            eng.close()
    return 0


def main() -> int:
    import os
    import tempfile

    mode = os.environ.get("FEI_TPU_FLEET_SMOKE_MODE", "").lower()
    if mode in ("kv", "kvtier"):
        return kv_main()
    if mode == "kvcdn":
        return kvcdn_main()

    # QoS env must land before any engine builds its TenantBook
    os.environ.setdefault("FEI_TPU_TENANT_BUDGETS",
                          "gold:4,silver:2,bronze:1")
    os.environ.setdefault("FEI_TPU_MAX_QUEUE", "4")

    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.engine.engine import InferenceEngine
    from fei_tpu.engine.faults import FAULTS
    from fei_tpu.fleet import InProcessReplica, Router
    from fei_tpu.ui.server import ServeAPI
    from fei_tpu.utils.metrics import METRICS

    def factory():
        engine = InferenceEngine.from_config(
            "tiny", paged=True, batch_size=2, page_size=16, max_seq_len=256,
        )
        return ServeAPI(JaxLocalProvider(engine=engine), model_name="fleet")

    replicas = [
        InProcessReplica(
            f"r{i}", factory=factory,
            drain_dir=tempfile.mkdtemp(prefix=f"fei-fleet-smoke-r{i}-"),
        )
        for i in range(2)
    ]
    router = Router(
        replicas, retries=2, backoff_s=0.02, breaker_fails=3,
        breaker_cooldown_s=0.4, health_ttl_s=0.1,
    )

    tenants = [("gold", 2), ("silver", 1), ("bronze", 0)]

    def complete(i: int, tenant: str, priority: int,
                 max_attempts: int = 40) -> tuple[bool, str]:
        """One request, retrying client-side on backpressure (the 429/503
        contract). True when it reached 200."""
        body = {
            "messages": [{"role": "user",
                          "content": f"smoke {tenant} {i}"}],
            "max_tokens": 4, "temperature": 0,
            "tenant": tenant, "priority": priority,
            "session": f"{tenant}-{i}",
        }
        last = "no attempt"
        for _ in range(max_attempts):
            res = router.handle("POST", "/v1/chat/completions", body, {})
            status, payload = res[0], res[1]
            if status == 200:
                return True, "ok"
            last = f"{status}: {payload}"
            time.sleep(0.05)
        return False, last

    # --- 1. mixed-tenant load: zero accepted-request loss ------------------
    n = 9
    outcomes: list = [None] * n

    def worker(i: int) -> None:
        tenant, priority = tenants[i % len(tenants)]
        outcomes[i] = complete(i, tenant, priority)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    [t.start() for t in threads]
    [t.join(timeout=300) for t in threads]
    bad = [(i, o) for i, o in enumerate(outcomes) if not (o and o[0])]
    if bad:
        return fail(f"mixed-tenant load lost requests: {bad}")
    print(f"fleet smoke: load ok — {n} mixed-tenant requests all reached 200")

    # --- 2. breaker eject -> half-open readmit round-trip ------------------
    c0 = METRICS.snapshot()["counters"]
    # fired() is cumulative; an env-armed chaos fault may already have
    # consumed fires at this point during phase 1
    fired0 = FAULTS.fired("router.forward")
    FAULTS.arm("router.forward", "conn", count=3,
               match=lambda ctx: ctx.get("replica") == "r0")
    # pace requests past the health-probe TTL so r0 re-enters rotation
    # between failures and the armed count actually drains to the
    # breaker threshold; every request must still land via r1
    deadline = time.time() + 15.0
    i = 0
    while (FAULTS.fired("router.forward") - fired0 < 3
           and time.time() < deadline):
        ok, why = complete(100 + i, "gold", 2)
        if not ok:
            return fail(f"request lost during breaker trip: {why}")
        i += 1
        time.sleep(0.12)
    c1 = METRICS.snapshot()["counters"]
    ejections = c1.get("router.ejections", 0) - c0.get("router.ejections", 0)
    if ejections < 1:
        return fail(
            f"breaker never opened (fired={FAULTS.fired('router.forward')}, "
            f"state={router._status_payload()})"
        )
    deadline = time.time() + 10.0
    readmitted = False
    while time.time() < deadline:
        router._candidates()  # half-open probe runs once the cooldown ends
        c2 = METRICS.snapshot()["counters"]
        if c2.get("router.readmissions", 0) > c0.get("router.readmissions", 0):
            readmitted = True
            break
        time.sleep(0.1)
    if not readmitted:
        return fail(f"r0 never readmitted: {router._status_payload()}")
    ok, why = complete(199, "gold", 2)
    if not ok:
        return fail(f"request lost after readmission: {why}")
    print("fleet smoke: breaker ok — r0 ejected then readmitted "
          f"(+{ejections} ejections)")

    # --- 3. rolling restart under streaming load: zero drops ---------------
    from fei_tpu.fleet.router import _parse_sse

    results: list = []
    res_lock = threading.Lock()
    stop = threading.Event()

    def stream_worker(idx: int) -> None:
        tenant, priority = tenants[idx % len(tenants)]
        r = 0
        while not stop.is_set():
            body = {
                "messages": [{"role": "user",
                              "content": f"restart {tenant} {idx} {r}"}],
                "max_tokens": 4, "temperature": 0,
                "tenant": tenant, "priority": priority,
            }
            tokens, err = 0, None
            for chunk in router.stream_chat(body, {}):
                info = _parse_sse(chunk)
                if info is None:
                    continue
                if info.get("error"):
                    err = info["error"]
                    break
                delta = (info.get("choices") or [{}])[0].get("delta") or {}
                if delta.get("content"):
                    tokens += 1
            with res_lock:
                results.append((tokens, err))
            r += 1
            time.sleep(0.02)

    workers = [threading.Thread(target=stream_worker, args=(i,))
               for i in range(4)]
    [w.start() for w in workers]
    time.sleep(0.5)
    report = router.rolling_restart(drain_deadline_s=60.0, wait_s=120.0)
    time.sleep(0.5)
    stop.set()
    [w.join(timeout=300) for w in workers]
    if not all(v.get("healthy") for v in report.values()):
        return fail(f"a replica did not come back healthy: {report}")
    dropped = [r for r in results if r[0] > 0 and r[1] is not None]
    if dropped:
        return fail(
            f"{len(dropped)} accepted stream(s) dropped mid-restart: "
            f"{dropped[:3]}"
        )
    served = sum(1 for r in results if r[1] is None and r[0] > 0)
    if served == 0:
        return fail(f"no stream served during the restart window: {results}")
    restored = sum(v.get("restored", 0) for v in report.values())
    print(
        f"fleet smoke: restart ok — {served} streams served, "
        f"0 accepted drops, {restored} snapshot(s) warm-restored, "
        f"report={report}"
    )

    c = METRICS.snapshot()["counters"]
    print(
        "fleet smoke: OK — requests="
        f"{int(c.get('router.requests', 0))} "
        f"retries={int(c.get('router.retries', 0))} "
        f"ejections={int(c.get('router.ejections', 0))} "
        f"readmissions={int(c.get('router.readmissions', 0))} "
        f"restarts={int(c.get('router.rolling_restarts', 0))}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
