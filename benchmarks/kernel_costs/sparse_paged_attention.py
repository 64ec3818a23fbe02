"""Decode attention over a selected page list (``sparse_paged_attention``:
the decode kernel of ``ops/pallas/paged_attention.py`` on the pages a
block-sparse layer's query picked, a list a kv head). A sequence of ``c``
tokens holds ``ceil(c / block)`` blocks; its query reads ``min(topk,
blocks)`` of them, each full but the last, which holds the query's own
position. Bytes: K and V of the keys read, q in, out back. Operations: q.K
and p.V, two multiply-adds per key read, head and dim. Only what the
algorithm needs; the layers counted are those of kind ``minicpm4``.
``contexts`` are the dispatch's own (``ctx`` of its flight record)."""


def cost(cfg: dict, contexts, n_steps: int, first_step: int = 0) -> dict:
    H, K, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    sp = cfg["assumed"]["sparse_config"]
    block, topk = sp["block_size"], sp["topk"]
    layers = sum(k == "minicpm4" for k in cfg["mixer_types"])
    elt = 2  # bfloat16 pages, q and out
    total_b = total_f = 0.0
    for step in range(first_step, first_step + n_steps):
        for c in contexts:
            n = c + step  # keys behind the query, its own among them
            blocks = -(-n // block)
            keys = (min(topk, blocks) - 1) * block + (n - 1) % block + 1
            total_b += layers * (2 * keys * K * d * elt + 2 * H * d * elt)
            total_f += layers * (4 * keys * H * d)
    return {"bytes": total_b, "flops": total_f}
