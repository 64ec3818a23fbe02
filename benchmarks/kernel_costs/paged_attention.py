"""Decode-path paged attention: one query row per sequence reads that
sequence's live K and V (inside its length and the sliding window) once
per layer and step. Bytes: K and V of the live tokens, q in, out back.
Operations: q.K and p.V, two multiply-adds per live token, head and dim.
Only what the algorithm needs: no padding to pages, no dead pages."""


def cost(cfg: dict, contexts, n_steps: int, first_step: int = 0) -> dict:
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    K = cfg.get("num_key_value_heads") or H
    d = cfg.get("head_dim") or h // H
    L = cfg["num_hidden_layers"]
    window = cfg.get("sliding_window") or 0
    elt = 2  # bfloat16 pages, q and out
    total_b = total_f = 0.0
    for step in range(first_step, first_step + n_steps):
        for c in contexts:
            live = c + step
            if window:
                live = min(live, window)
            total_b += L * (2 * live * K * d * elt + 2 * H * d * elt)
            total_f += L * (4 * live * H * d)
    return {"bytes": total_b, "flops": total_f}
