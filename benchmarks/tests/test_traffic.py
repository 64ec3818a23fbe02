import json
import os

import pytest

from benchmarks import loadgen, tokenizer as tk

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def _load(name):
    with open(os.path.join(TRAFFIC, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["rehearsal_open"])
def test_open_schedule_is_a_function_of_the_seed(name):
    t = _load(name)
    a = loadgen.poisson_schedule(t, 2**31 + 11, 40.0, 51200)
    b = loadgen.poisson_schedule(t, 2**31 + 11, 40.0, 51200)
    c = loadgen.poisson_schedule(t, 5, 40.0, 51200)
    assert a == b and a != c
    # the schedule is the mix's, the content the seed's
    assert [(p["due"], p["max_tokens"], len(p["turns"][0][1])) for p in a] == \
        [(p["due"], p["max_tokens"], len(p["turns"][0][1])) for p in c]
    assert all(pa["turns"] != pc["turns"] for pa, pc in zip(a, c))
    for plan in (a, c):
        dues = [p["due"] for p in plan]
        assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 40.0
        for p in plan:
            n = len(p["turns"][0][1]) + tk.template_overhead(1)
            assert t["prompt_tokens"]["min"] <= n <= t["prompt_tokens"]["max"]
            assert t["max_tokens"]["min"] <= p["max_tokens"] <= t["max_tokens"]["max"]
            assert all(tk.FIRST_CONTENT_ID <= i < 51200 for i in p["turns"][0][1])
    other = loadgen.poisson_schedule(dict(t, schedule_seed=2), 5, 40.0, 51200)
    assert [p["due"] for p in other] != [p["due"] for p in c]


@pytest.mark.parametrize("name", ["agent_sessions", "rehearsal_sessions"])
def test_session_scripts_are_a_function_of_the_seed(name):
    t = _load(name)
    big = 3000000000
    assert loadgen.shared_system(t, big, 32000) == loadgen.shared_system(t, big, 32000)
    assert loadgen.shared_system(t, big, 32000) != loadgen.shared_system(t, 4, 32000)
    assert len(loadgen.shared_system(t, big, 32000)) + 3 == t["system_prompt_tokens"]
    assert loadgen.session_offsets(t, big) == loadgen.session_offsets(t, big)
    assert all(0 <= o <= t["start_spread_s"] for o in loadgen.session_offsets(t, big))
    sizes = set()
    for seed in (big, 4):
        for p in range(6):
            plan = loadgen.session_plan(t, seed, 32000, p)
            assert plan == loadgen.session_plan(t, seed, 32000, p)
            assert t["task_tokens"]["min"] <= len(plan["task"]) <= t["task_tokens"]["max"]
            assert len(plan["turns"]) == t["turns_per_session"]
            for s in plan["turns"]:
                assert t["max_tokens"]["min"] <= s["max_tokens"] <= t["max_tokens"]["max"]
                assert len(s["stand_in"]) == s["max_tokens"]
                assert (t["tool_result_tokens"]["min"] <= len(s["tool"])
                        <= t["tool_result_tokens"]["max"])
            sizes.add(tuple(sorted(s["max_tokens"] for s in plan["turns"])))
            twin = loadgen.session_plan(t, seed + 1, 32000, p)
            assert [len(s["tool"]) for s in twin["turns"]] == \
                [len(s["tool"]) for s in plan["turns"]]
            assert twin["turns"][0]["tool"] != plan["turns"][0]["tool"]
            # balanced: every run of four turns holds a small and a large reply
            outs = [s["max_tokens"] for s in plan["turns"]]
            q = sorted(outs)
            for k in range(0, len(outs) - 3 if len(outs) % 4 == 0 else 0, 4):
                block = outs[k:k + 4]
                assert min(block) <= q[len(q) // 4] and max(block) >= q[-len(q) // 4]
    # each script: the same set of turn sizes in its own order
    assert len(sizes) == 1


def test_sessions_under_way_and_their_priming():
    t = _load("agent_sessions")
    starts = [loadgen.start_turn(t, s) for s in range(t["sessions"])]
    assert starts == [0, 3, 6, 9]
    bodies = loadgen.prime_bodies(t, 11, 32000)
    assert len(bodies) == 1 + sum(1 for s in starts if s)
    system = loadgen.shared_system(t, 11, 32000)
    for slot, body in zip([s for s in range(4) if starts[s]], bodies[1:]):
        plan = loadgen.session_plan(t, 11, 32000, slot)
        first = loadgen.conversation(system, plan, starts[slot])
        # primed: everything before the tool result the first turn brings
        assert [m["role"] for m in body["messages"]] == [r for r, _ in first[:-1]]
        assert body["messages"][-1]["role"] == "assistant" and body["max_tokens"] == 1
        n = sum(len(c) for _, c in first) + tk.template_overhead(len(first))
        assert n <= t["max_prompt_tokens"]


def test_a_whole_session_fits_under_the_prompt_limit():
    t = _load("agent_sessions")
    system = loadgen.shared_system(t, 9, 32000)
    for p in range(4):
        plan = loadgen.session_plan(t, 9, 32000, p)
        last = loadgen.conversation(system, plan, t["turns_per_session"] - 1)
        n = sum(len(c) for _, c in last) + tk.template_overhead(len(last))
        assert n <= t["max_prompt_tokens"], n


def test_balanced_order_keeps_the_values():
    import random

    vals = list(range(33))
    out = loadgen.balanced_order(vals, random.Random(3))
    assert sorted(out) == vals
    for k in range(0, 32, 4):
        assert sorted(v // 8 for v in out[k:k + 4]) in ([0, 1, 2, 3], [0, 1, 2, 4])


def test_short_prompt_grid_reaches_every_size_class():
    t = _load("rehearsal_open")
    grid = loadgen.short_prompt_lengths(t, 256)
    classes = {(max(16, 1 << (n - 1).bit_length()), -(-n // 64))
               for n in range(t["prompt_tokens"]["min"], 257)}
    reached = {(max(16, 1 << (n - 1).bit_length()), -(-n // 64)) for n in grid}
    assert classes == reached
    assert loadgen.short_prompt_lengths(_load("agent_sessions"), 256) == []
