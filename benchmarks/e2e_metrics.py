"""End-to-end metric arithmetic over the load generator's records.

All times are the load generator's clock, relative to the window's start.
Every request due inside the window counts in ``attempted``; one that was
refused, shed, errored, cut short or cancelled counts in ``failed`` and
misses: where it never showed a token, its time to first token is the
whole of its deadline.

The window has a hard end, and whether a closed loop's last turn falls due
just inside it hangs on tens of milliseconds. So the numbers that are
judged move little with one request more or less: the time to first token
is a mean (the median of some thirty clustered values jumps by the gap
between two neighbours), and stream time and tokens are taken inside the
window, where the cell's load is on, and not in the drain behind it.
"""

from __future__ import annotations

import statistics


def _pct(values: list[float], q: int) -> float:
    """The q-th percentile (q a multiple of 10), inclusive method."""
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=10, method="inclusive")[q // 10 - 1]


def ttft_ms(records: list[dict], miss_s: float) -> list[float]:
    out = []
    for r in records:
        if r["t_tok"]:
            out.append(1e3 * (r["t_tok"][0] - r["due"]))
        else:
            out.append(1e3 * miss_s)
    return out


BURST_S = 0.002  # arrivals of one stream this close together are one burst


def tokens_inside(rec: dict, seconds: float) -> float:
    """Output tokens of one stream delivered inside the window. Tokens
    reach a client in bursts (a scan of several steps, one frame), so a
    plain count at the window's end moves by whole bursts or not at all.
    A burst of k tokens that arrives at ``a``, after the stream's previous
    burst at ``p`` (or the time the request was due), counts as k tokens
    delivered evenly over (p, a]: the burst that straddles the window's
    end counts by the share of its interval that lies inside."""
    total, prev, t = 0.0, rec["due"], rec["t_tok"]
    i = 0
    while i < len(t) and prev < seconds:
        j = i + 1
        while j < len(t) and t[j] - t[i] <= BURST_S:
            j += 1
        a, k = t[j - 1], j - i
        total += k if a <= seconds else k * (seconds - prev) / (a - prev)
        prev, i = a, j
    return total


def summarize(records: list[dict], seconds: float, chips: int,
              miss_s: float) -> dict:
    """Every end-to-end number a cell may report, by metric name."""
    recs = [r for r in records if r["due"] < seconds]
    ttft = ttft_ms(recs, miss_s)
    # a stream's tokens after its first, and the time they took, as far as
    # they arrived inside the window
    inside = [[t for t in r["t_tok"] if t < seconds] for r in recs]
    stream_s = sum(t[-1] - t[0] for t in inside if len(t) > 1)
    after_first = sum(len(t) - 1 for t in inside if len(t) > 1)
    in_window = sum(tokens_inside(r, seconds) for r in recs)
    out = {
        "attempted": len(recs),
        "failed": sum(1 for r in recs if r["status"] != "ok"),
    }
    if in_window:
        out["tok_s_per_chip"] = in_window / seconds / chips
    if ttft:
        out["ttft_ms_mean"] = sum(ttft) / len(ttft)
        out["ttft_ms_p50"] = _pct(ttft, 50)
        out["ttft_ms_p90"] = _pct(ttft, 90)
    if after_first:
        out["tpot_ms"] = 1e3 * stream_s / after_first
    return out
