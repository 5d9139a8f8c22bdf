"""The system under test, built from a configuration file.

The benchmark reaches the program only here and in ``harness.py``: it
builds the served path a user builds -- ``PaxosContext`` with its default
engine (the Pallas wire path on a TPU), ``ConsensusService`` sessions, and
for a KV deployment ``ReplicatedKV`` -- and reads the program's own
counters and logs.  The program's ``src/`` is found beside ``bench/``.
"""
from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def import_program() -> None:
    """Put the program's ``src/`` on the path; fail if it is not there."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"the program's sources are not at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


class System:
    """One deployment: context, service, and the KV tier where it has one.

    ``delivered`` collects every payload the context delivers, in order;
    the harness drains it after each call into the program."""

    def __init__(self, config: dict, chips: int):
        import_program()
        from repro.core import PaxosConfig, PaxosContext
        from repro.serve import ConsensusService, ReplicatedKV

        p = config["paxos"]
        self.cfg = PaxosConfig(
            n_acceptors=p["n_acceptors"],
            n_instances=p["n_instances"],
            value_words=p["value_words"],
            batch=p["batch"],
            n_groups=p["n_groups"],
        )
        mesh = None
        if config.get("sharded"):
            from repro.launch.mesh import make_group_mesh

            mesh = make_group_mesh(chips)
        ctx_opts = config.get("context", {})
        self.ctx = PaxosContext(
            self.cfg,
            fused=bool(ctx_opts.get("fused", False)),
            snapshots=bool(ctx_opts.get("snapshots", False)),
            mesh=mesh,
        )
        self.delivered: list[bytes] = []
        sink = self.delivered.append
        self.ctx.deliver_cb = lambda payload, _n, _inst: sink(payload)
        self.svc = ConsensusService(self.ctx)
        self.kv = ReplicatedKV(self.svc) if config["front_end"] == "kv" else None
        self.hw = self.ctx.hw
        self.n_groups = self.cfg.n_groups
        self.n_instances = self.cfg.n_instances

    def seq_marks(self) -> list[int]:
        """Per-group sequencer watermarks (host mirrors, no device sync)."""
        return self.hw._seq_marks()

    def watermark(self, gid: int) -> int:
        return self.ctx.snapshots.watermark(gid)

    def log_len(self, gid: int) -> int:
        """Length of the group's stitched log (snapshot prefix + live)."""
        n = len(self.ctx.group_log[gid])
        if self.ctx.snapshots is not None:
            n += len(self.ctx.snapshots.log_prefix(gid))
        return n

    def dispatch_counts(self) -> dict:
        hw = self.hw
        return {
            "dispatch": hw.dispatch_count,
            "jnp": hw.jnp_dispatch_count,
            "persistent": hw.persistent_dispatch_count,
        }
