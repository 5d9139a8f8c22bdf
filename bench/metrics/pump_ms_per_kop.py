"""Service and context pump: host milliseconds in ``ConsensusService.pump``
and ``PaxosContext.submit`` per 1000 acknowledged ops (harness spans; the
pump's time includes the dispatches it waits for)."""


def read(r: dict):
    spans = r.get("spans", {})
    total = sum(spans[n][0] for n in ("svc.pump", "ctx.submit") if n in spans)
    if not total or not r.get("acked"):
        return None
    return total * 1e6 / r["acked"]
