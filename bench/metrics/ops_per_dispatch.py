"""Planner and dataplane: acknowledged ops per device program launch in
the window (the dataplane's own ``dispatch_count``)."""


def read(r: dict):
    n = r.get("dispatches", {}).get("dispatch", 0)
    if not n:
        return None
    return r["acked"] / n
