"""The selective state-space recurrence of a Mamba-2 mixer (the
``ssm_state`` scope of ``fei_tpu/models/falcon_h1.py``: ``ops/ssd.step`` in
a decode step, ``ops/ssd.chunked`` in an admission chunk), whatever
computes it. What any implementation must move for one live row, layer and
step: the float32 state ``[heads, d_head, d_state]`` read once and written
once, x, B, C and dt in and y out (activations of two bytes, dt four);
never an idle slot's row. Operations: a head's state element is decayed,
takes its share of ``dt x (outer) B`` and is read out against C, five
operations; the skip adds two a channel.

``state_rows``: live rows times steps, summed over a dispatch's steps (its
flight record's ``state_rows``); the layers come from the configuration.
``chunk_tokens``: real tokens of an admission chunk riding the dispatch:
its row of the state is read and written once a layer too, and each of its
tokens brings the same inputs and operations as a decode row's."""


def cost(cfg: dict, state_rows: int, chunk_tokens: int = 0) -> dict:
    L = cfg["num_hidden_layers"]
    nh, dh, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    G, ds = cfg["mamba_n_groups"], cfg["mamba_d_ssm"]
    state = nh * dh * N * 4
    token = 2 * ds * 2 + 2 * G * N * 2 + nh * 4  # x in, y out; B, C; dt
    flops = 5 * nh * dh * N + 2 * ds
    rows = state_rows + (1 if chunk_tokens else 0)
    tokens = state_rows + chunk_tokens
    return {"bytes": float(L * (rows * 2 * state + tokens * token)),
            "flops": float(L * tokens * flops)}
