"""The ragged kernel (``ragged_paged_attention``) in a merged dispatch:
one call per layer serves the decode rows of the batch and the rows of an
admission chunk. Only what the algorithm needs, as in ``paged_attention``.

Decode rows: one query row per sequence reads that sequence's live K and V
(inside its length and the sliding window), q in, out back; two
multiply-adds per live key, head and dim.

Chunk rows: the chunk's ``n`` tokens sit at positions ``lo`` .. ``lo + n -
1`` of their request. K and V of the ``min(lo + n, window)`` tokens they
attend are read once for all rows; q in and out back per row; each row
attends the keys up to its own position (causal within the chunk), inside
the window."""


def cost(cfg: dict, contexts, chunk_lo: int, chunk_tokens: int) -> dict:
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    K = cfg.get("num_key_value_heads") or H
    d = cfg.get("head_dim") or h // H
    L = cfg["num_hidden_layers"]
    window = cfg.get("sliding_window") or 0
    elt = 2  # bfloat16 pages, q and out

    def live(n_keys):
        return min(n_keys, window) if window else n_keys

    total_b = total_f = 0.0
    for c in contexts:
        total_b += 2 * live(c) * K * d * elt + 2 * H * d * elt
        total_f += 4 * live(c) * H * d
    if chunk_tokens:
        total_b += 2 * live(chunk_lo + chunk_tokens) * K * d * elt
        total_b += 2 * chunk_tokens * H * d * elt
        total_f += sum(4 * live(chunk_lo + i + 1) * H * d
                       for i in range(chunk_tokens))
    return {"bytes": L * total_b, "flops": L * total_f}
