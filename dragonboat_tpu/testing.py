"""Shared full-stack probe harness (used by the driver's multichip
dry-run hook and the sharding test suite — one copy, so the election-wait
and propose protocol cannot drift between them).

Reference analog: ``internal/tests`` ships the fake SMs every test layer
reuses; this module plays the same role for the in-process 3-NodeHost
stack shape.
"""
from __future__ import annotations

import time

from . import Config, NodeHostConfig, Result
from .config import ExpertConfig
from .nodehost import NodeHost
from .transport import ChanRouter, ChanTransport


class CounterSM:
    """Minimal counter state machine for stack probes.

    Process-spawnable (ISSUE 12): living in an importable module — not
    a bench/test ``__main__`` — lets the hostproc apply tier rebuild it
    inside a worker from its ``module:qualname`` spec."""

    __hostproc_spawnable__ = True

    def __init__(self, cluster_id, node_id):
        self.v = 0

    def update(self, cmd):
        self.v += 1
        return Result(value=self.v)

    def lookup(self, query):
        return self.v

    def save_snapshot(self, w, files, done):
        w.write(self.v.to_bytes(8, "little"))

    def recover_from_snapshot(self, r, files, done):
        self.v = int.from_bytes(r.read(8), "little")

    def close(self):
        pass


def run_sharded_stack_check(
    n_devices: int,
    groups: int = 16,
    writes_per_group: int = 5,
    rtt_ms: int = 20,
    election_wait_s: float = 90.0,
) -> int:
    """3 in-process NodeHosts (chan transport) whose quorum engines are
    group-sharded over ``n_devices`` (``ExpertConfig.engine_mesh_devices``):
    real coordinator registration/staging/rounds, device-tick elections,
    and ``writes_per_group`` committed proposals per group.  Returns the
    total committed write count; raises on any failure.

    ``n_devices`` is capped at the host's core count: each mesh shard
    carries its own dispatch-stream thread, and this check builds THREE
    coordinators, so 8 virtual shards on a 2-vCPU CI box means 24
    dispatch threads thrashing 2 cores — measured 386s vs 12s for the
    identical check at one stream per core.  Wide-mesh coverage (8
    shards, single engine) lives in tests/test_mesh_dispatch.py and the
    bench mesh_axis rung, which don't triple the stream count."""
    import os

    from .ops.sharding import GROUP_AXIS

    n_devices = min(n_devices, max(2, os.cpu_count() or 2))
    while groups % n_devices:
        n_devices -= 1

    router = ChanRouter()
    addrs = {i: f"mc{i}:1" for i in (1, 2, 3)}
    cids = list(range(500, 500 + groups))
    nhs = []
    try:
        for i in (1, 2, 3):
            nhs.append(NodeHost(NodeHostConfig(
                node_host_dir=":memory:", rtt_millisecond=rtt_ms,
                raft_address=addrs[i],
                raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                    s, rh, ch, router=router
                ),
                expert=ExpertConfig(
                    quorum_engine="tpu", engine_block_groups=groups,
                    engine_mesh_devices=n_devices,
                ),
            )))
        for nh in nhs:
            # SingleDeviceSharding has no .spec — fail with the
            # diagnostic, not an AttributeError
            spec = getattr(
                nh.quorum_coordinator.eng.dev.match.sharding, "spec", None
            )
            assert spec and spec[0] == GROUP_AXIS, (
                f"engine not group-sharded: {spec}"
            )
        for i, nh in enumerate(nhs, 1):
            for cid in cids:
                nh.start_cluster(addrs, False, CounterSM, Config(
                    cluster_id=cid, node_id=i, election_rtt=10,
                    heartbeat_rtt=1,
                ))
        deadline = time.time() + election_wait_s
        led = {}
        while len(led) < len(cids) and time.time() < deadline:
            for cid in cids:
                if cid in led:
                    continue
                for nh in nhs:
                    lid, ok = nh.get_leader_id(cid)
                    if ok:
                        led[cid] = nhs[lid - 1]
                        break
            time.sleep(0.02)
        assert len(led) == len(cids), (
            f"sharded-stack elections: {len(led)}/{len(cids)}"
        )
        total = 0
        for cid, leader in led.items():
            s = leader.get_noop_session(cid)
            for k in range(writes_per_group):
                r = leader.sync_propose(s, b"x", timeout=10.0)
                assert r.value == k + 1
                total += 1
        return total
    finally:
        for nh in nhs:
            nh.stop()
