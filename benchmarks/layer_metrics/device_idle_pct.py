"""Device: 1 - union of device-operation intervals over the traced
interval (first to last device operation of the trace)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.get("window_s") or tr.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
