"""The system under test: a deployment's in-process NodeHosts (three or
five) on one chip.

Bring-up follows ``chip_smoke.live_phase`` (copied, not imported: the
benchmark may name no file outside its own directory): ``quorum_engine=
"tpu"``, ``fast_lane=False``, ``ChanTransport``, durable ``node_host_dir``
with fsync honoured, all hosts sharing the one chip in the one process.
Leaders are placed by explicit campaigns.

``LiveCluster`` is the narrow surface the generator drives; the plain
reference (``reference/kv.py``) and the test fakes implement the same four
methods, so any of them can stand in the program's place.
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import threading
import time

class KV:
    """The client's state machine: a proposal is a key and a value of the
    configuration's ``key_bytes`` and ``value_bytes`` (the source's 16-byte
    payload is 8 + 8).  ``updates`` counts applied proposals, duplicates
    from retried attempts included."""

    key_bytes = value_bytes = 8  # LiveCluster sets the configuration's

    def __init__(self, cluster_id, node_id):
        self.kv = {}
        self.updates = 0

    def update(self, cmd):
        from dragonboat_tpu import Result

        self.kv[bytes(cmd[:self.key_bytes])] = bytes(cmd[self.key_bytes:])
        self.updates += 1
        return Result(value=self.updates)

    def lookup(self, query):
        return self.kv.get(bytes(query))

    def save_snapshot(self, w, files, done):
        blob = b"".join(k + v for k, v in sorted(self.kv.items()))
        w.write(len(blob).to_bytes(8, "little") + blob)

    def recover_from_snapshot(self, r, files, done):
        n = int.from_bytes(r.read(8), "little")
        blob = r.read(n)
        step = self.key_bytes + self.value_bytes
        self.kv = {
            blob[i:i + self.key_bytes]: blob[i + self.key_bytes:i + step]
            for i in range(0, n, step)
        }

    def close(self):
        pass


class LeaderLog:
    """``raft_event_listener`` of every host: the generator's leader map and
    the source of ``leader_changes``.  Called on raft threads, so O(1)."""

    def __init__(self):
        self.leader = {}      # cid -> node id of the newest known leader
        self.term = {}        # cid -> term of that leader
        self.events = []      # (perf_counter, cid, term, leader_id)
        self.terms = {}       # cid -> leader terms seen since start

    def leader_updated(self, info):
        if not info.leader_id:
            return
        if info.term > self.term.get(info.cluster_id, -1):
            self.term[info.cluster_id] = info.term
            self.leader[info.cluster_id] = info.leader_id
            self.events.append(
                (time.perf_counter(), info.cluster_id, info.term,
                 info.leader_id)
            )
            self.terms[info.cluster_id] = self.terms.get(info.cluster_id, 0) + 1


def wait_until(pred, timeout_s, what, poll_s=0.02):
    t0 = time.perf_counter()
    while not pred():
        if time.perf_counter() - t0 > timeout_s:
            raise TimeoutError(f"{what} not reached in {timeout_s}s")
        time.sleep(poll_s)
    return time.perf_counter() - t0


#: the ``Config`` fields the harness sets itself: the two identities, and
#: the two clocks that a configuration states under ``assumed``
OWNED = ("cluster_id", "node_id", "election_rtt", "heartbeat_rtt")


def group_config(config: dict) -> dict:
    """Every keyword but the two identities of the ``Config`` each replica
    is started with: the clocks under ``assumed`` and the configuration's
    optional ``group_config`` block, ``Config`` field names to JSON scalars
    (``check_quorum``, ``snapshot_entries``, ``compaction_overhead``,
    ``read_lease``, ...).  Absent or empty, the ``Config`` built is the one
    built before the key existed.  A key that is no field of ``Config``, one
    the harness owns, or a value that is no ``bool`` or ``int`` raises
    ``ValueError`` naming it; what the fields mean together is
    ``Config.validate``'s, which ``start_cluster`` runs."""
    from dragonboat_tpu import Config

    fields = {f.name for f in dataclasses.fields(Config)}
    block = config.get("group_config", {})
    if not isinstance(block, dict):
        raise ValueError(f"group_config is not an object: {block!r}")
    for key, value in block.items():
        if key not in fields:
            raise ValueError(f"group_config: {key!r} is not a field of Config")
        if key in OWNED:
            raise ValueError(f"group_config: {key!r} is the harness's "
                             "(the clocks are stated under 'assumed')")
        if not isinstance(value, (bool, int)):
            raise ValueError(f"group_config: {key!r} is not a bool or an int: "
                             f"{value!r}")
    assumed = config["assumed"]
    return dict(block, election_rtt=assumed["election_rtt"],
                heartbeat_rtt=assumed["heartbeat_rtt"])


class LiveCluster:
    """The NodeHosts of one deployment (a ``configs/*.json``): three, or as
    many as it has ``replicas``."""

    def __init__(self, config: dict, cache_dir: str, trace_sample_every=0,
                 sm_class=KV):
        from dragonboat_tpu import Config, NodeHostConfig
        from dragonboat_tpu.config import ExpertConfig
        from dragonboat_tpu.nodehost import NodeHost
        from dragonboat_tpu.requests import RequestError
        from dragonboat_tpu.transport import ChanRouter, ChanTransport

        settings = group_config(config)  # raises before a NodeHost exists
        self.busy_errors = (RequestError,)
        self.groups = int(config["groups"])
        self.replicas = int(config["replicas"])
        self.key_bytes = int(config["key_bytes"])
        self.value_bytes = int(config["value_bytes"])
        if self.key_bytes + self.value_bytes != int(config["payload_bytes"]):
            raise ValueError("key_bytes + value_bytes is not payload_bytes")
        assumed = config["assumed"]
        self.rtt_s = assumed["rtt_millisecond"] / 1000.0
        self.cids = list(range(1, self.groups + 1))
        self.leaders = LeaderLog()
        self.sms = {}  # (cid, node_id) -> the KV instance the program drives
        self.phases = {}
        self._base = tempfile.mkdtemp(prefix="bench-nh-")
        self.nhs = []

        def make_sm(cluster_id, node_id):
            sm = sm_class(cluster_id, node_id)
            sm.key_bytes, sm.value_bytes = self.key_bytes, self.value_bytes
            self.sms[(cluster_id, node_id)] = sm
            return sm

        router = ChanRouter()
        addrs = {i: f"bench{i}:1" for i in range(1, self.replicas + 1)}
        t0 = time.perf_counter()
        try:
            for i in addrs:
                self.nhs.append(NodeHost(NodeHostConfig(
                    node_host_dir=f"{self._base}/nh{i}",
                    rtt_millisecond=assumed["rtt_millisecond"],
                    raft_address=addrs[i],
                    raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                        src, rh, ch, router=router
                    ),
                    raft_event_listener=self.leaders,
                    compilation_cache_dir=cache_dir,
                    trace_sample_every=trace_sample_every,
                    expert=ExpertConfig(
                        quorum_engine="tpu", fast_lane=False,
                        engine_block_groups=assumed["engine_block_groups"],
                    ),
                )))
            self.phases["nodehosts_s"] = time.perf_counter() - t0
            t1 = time.perf_counter()
            # group by group, so that a group's replicas come up together
            # and none campaigns for minutes against peers not started yet
            for cid in self.cids:
                for i, nh in enumerate(self.nhs, start=1):
                    nh.start_cluster(
                        addrs, False, make_sm,
                        Config(cluster_id=cid, node_id=i, **settings),
                    )
            self.phases["start_cluster_s"] = time.perf_counter() - t1
            self.coords = [nh.quorum_coordinator for nh in self.nhs]
            if any(c is None for c in self.coords):
                raise RuntimeError("a NodeHost has no device quorum engine")
            # deterministic leader placement, spread evenly over the hosts
            for cid in self.cids:
                self.nhs[cid % self.replicas].get_node(cid).request_campaign()
            self.phases["election_s"] = wait_until(
                lambda: len(self.leaders.leader) == self.groups, 300.0,
                f"leaders for {self.groups} groups",
            )
            # the fused/single-round programs compile (or load from the
            # persistent cache) on a background thread per host; a
            # first-use compile on the round thread would stall commits
            self.phases["device_warmup_s"] = wait_until(
                lambda: all(c.eng.fused_ready for c in self.coords), 900.0,
                "fused warm-up on every host", poll_s=0.05,
            )
            for c in self.coords:
                if c.warmup_stats.get("error") is not None:
                    raise RuntimeError(f"device warm-up failed: {c.warmup_stats}")
        except BaseException:
            self.stop()
            raise

    # ---- the surface the generator drives --------------------------------

    def leader_host(self, cid: int) -> int:
        """Index of the host that leads ``cid`` at this moment."""
        return self.leaders.leader[cid] - 1

    def refresh_leader(self, cid: int) -> None:
        """After a failed attempt: ask the hosts, not the event log."""
        for nh in self.nhs:
            lid, ok = nh.get_leader_id(cid)
            if ok and 1 <= lid <= self.replicas:
                self.leaders.leader[cid] = lid
                return

    def submit_write(self, host: int, cid: int, cmd: bytes, timeout_s: float):
        nh = self.nhs[host]
        return nh.propose(nh.get_noop_session(cid), cmd, timeout_s)

    def submit_read(self, host: int, cid: int, timeout_s: float):
        return self.nhs[host].read_index(cid, timeout_s)

    def lookup(self, host: int, cid: int, key: bytes):
        """The tail of ``sync_read``: the local state machine, once the
        ReadIndex future completed."""
        return self.nhs[host].get_node(cid).sm.lookup(key)

    # ---- what the comparison reads once the window has closed ------------

    def wait_converged(self, timeout_s: float) -> float:
        """Every replica of every group at the same applied index."""

        def converged():
            for cid in self.cids:
                applied = {
                    nh.get_node(cid).sm.get_last_applied() for nh in self.nhs
                }
                if len(applied) != 1:
                    return False
            return True

        return wait_until(converged, timeout_s, "replica convergence", 0.05)

    def replica_contents(self, cid: int) -> list:
        return [self.sms[(cid, i)].kv for i in range(1, self.replicas + 1)]

    def device_commit(self) -> dict:
        """cid -> absolute commit index as the device engines hold it (what
        the kernels computed).  Every host's engine has a row for every
        group; the newest is the leader's, whichever host led last."""
        snaps = []
        for c in self.coords:
            with c._mu:  # a concurrent dispatch donates the state
                snaps.append(c.eng.committed_snapshot())
        return {cid: max(s[cid] for s in snaps) for cid in self.cids}

    def terms_seen(self, cid: int) -> int:
        return self.leaders.terms.get(cid, 0)

    def leader_changes(self, t0: float, t1: float) -> list:
        """(perf_counter, cid, term, leader) of every change in [t0, t1)."""
        return [e for e in self.leaders.events if t0 <= e[0] < t1]

    def sampled_traces(self) -> list:
        """The tracers' sampled requests (empty unless tracing is on)."""
        return [t for nh in self.nhs if nh.tracer is not None
                for t in nh.tracer.traces()]

    def state_leaves(self) -> list:
        """(shape, dtype) of every leaf of one host's device state."""
        import jax

        c = self.coords[0]
        with c._mu:
            return [
                (tuple(x.shape), str(x.dtype))
                for x in jax.tree_util.tree_leaves(c.eng.dev)
            ]

    def state_platforms(self) -> set:
        import jax

        out = set()
        for c in self.coords:
            with c._mu:
                for leaf in jax.tree_util.tree_leaves(c.eng.dev):
                    out |= {d.platform for d in leaf.devices()}
        return out

    def stop(self) -> None:
        stoppers = [threading.Thread(target=nh.stop) for nh in self.nhs]
        for t in stoppers:
            t.start()
        for t in stoppers:
            t.join(60.0)
        self.nhs = []
        shutil.rmtree(self._base, ignore_errors=True)
