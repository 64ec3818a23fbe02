"""The routed experts' grouped products (``moe_grouped_matmul``,
``fei_tpu/ops/pallas/grouped_matmul.py``, called for gate, up and down
under the ``moe_experts`` scope of ``fei_tpu/ops/moe.moe_held`` over the
rows in expert order). What any implementation must move for one layer and step: the
three matrices of every held expert that has at least one row (never all
the held experts when fewer are touched), their scales, each assigned
row in (hidden, once for gate and up) and out (hidden). Operations: three
products a row, two operations a multiply-add. ``touched`` and ``rows``
are sums over a dispatch's layers and steps (``experts_touched`` and
``held_rows`` of its flight record)."""


def cost(cfg: dict, touched: int, rows: int) -> dict:
    h, I = cfg["hidden_size"], cfg["moe_intermediate_size"]
    w_elt = 1 if cfg["weights"]["precision"] == "int8" else 2
    expert = 3 * h * I * w_elt + (2 * I + h) * 4  # matrices and their scales
    return {"bytes": float(touched * expert + rows * 2 * h * 2),
            "flops": float(rows * 3 * h * I * 2)}
