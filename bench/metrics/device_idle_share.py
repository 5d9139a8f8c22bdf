"""Device: the share of the traced window in which no operation ran on
the chip (1 less the union of device-op intervals over the window),
averaged over the chips the cell uses."""


def read(r: dict):
    tr = r.get("trace") or {}
    if not tr.get("window_s"):
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
