"""KV tier: host microseconds per acknowledged op spent in the KV client
itself -- ``KVSession.put``/``get`` and ``ReplicatedKV.refresh``, less the
pumps and submits nested in them (their self time, from harness spans)."""


def read(r: dict):
    spans = r.get("spans", {})
    own = [spans[n][1] for n in ("kv.put", "kv.get", "kv.refresh") if n in spans]
    if not own or not r.get("acked"):
        return None
    return sum(own) * 1e6 / r["acked"]
