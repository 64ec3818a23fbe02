"""Decode attention over a paged latent cache (``latent_paged_attention``,
``fei_tpu/ops/pallas/latent_paged_attention.py``): one program a sequence
reads that sequence's live rows once per layer and step, for all heads
together. A row is the compressed vector and the rotated key part, ``kv_lora_rank +
qk_rope_head_dim`` numbers (1,152 bytes in bfloat16 at Moonlight's sizes):
what is information, whatever padding the pool stores. Bytes: the live
rows, the absorbed queries in (heads x the row's width), the sums out
(heads x ``kv_lora_rank``). Operations: a score over the row's width and a
value sum over ``kv_lora_rank`` per live row and head, two operations a
multiply-add. ``contexts`` are the dispatch's own (``ctx`` of its flight
record: the active slots' lengths at the first step)."""


def cost(cfg: dict, contexts, n_steps: int, first_step: int = 0) -> dict:
    H, L = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    elt = 2  # bfloat16 rows, queries and sums
    total_b = total_f = 0.0
    for step in range(first_step, first_step + n_steps):
        for c in contexts:
            live = c + step
            total_b += L * elt * (live * (r + dr) + H * (r + dr) + H * r)
            total_f += L * 2 * live * H * ((r + dr) + r)
    return {"bytes": total_b, "flops": total_f}
