"""Entry (``ui/server.py``, ``agent/providers.py``): median over requests
of the client's time to first token minus the scheduler's own queued ->
first_token time of the same request (``obs/trace.py``). What is left is
HTTP, parsing, templating, tokenizing and framing. A request is matched
to its trace by prompt length and by when it was sent."""

import statistics


def read(ctx):
    traces = []
    for t in ctx["request_traces"]:
        ph = {s["phase"]: s["ts"] for s in t["spans"]}
        if "queued" in ph and "first_token" in ph:
            traces.append([t["prompt_tokens"], ph["queued"],
                           ph["first_token"] - ph["queued"], False])
    diffs = []
    for r in ctx["records"]:
        if r["status"] != "ok" or not r["t_tok"] or r.get("sent_wall") is None:
            continue
        best = None
        for t in traces:
            if t[3] or t[0] != r["prompt_tokens"]:
                continue
            lag = t[1] - r["sent_wall"]
            if -0.01 <= lag <= 5.0 and (best is None or lag < best[1] - r["sent_wall"]):
                best = t
        if best is None:
            continue
        best[3] = True
        client = r["t_tok"][0] - r["sent"]
        diffs.append(1e3 * (client - best[2]))
    return statistics.median(diffs) if diffs else None
