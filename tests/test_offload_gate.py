"""What opens ``Node.step_node``'s gate (ISSUE 40).

The coordinator's round thread only flags a device effect on a node
(``offload_*``) and wakes the group; the step worker's turn applies it.  The
gate of that turn is one fact that every flagger sets under ``_off_mu`` and
only the swap in ``_apply_offload_effects`` clears; the tests hold it to
what it does, not to its name.  On the parent the gate listed five of the seven fields, so a confirmed ReadIndex
context (``_off_reads``) and a scalar-path echo (``_off_read_echoes``) sat
through the woken turn and waited for the group's next heartbeat tick.

One NodeHost on the tpu engine, one single-voter group, a clock that never
ticks (``rtt_millisecond`` is 1000 s): nothing steps the group but the test,
so a turn is a count.  A turn is ``engine.process_steps([node])``: one
``step_node`` and what the step worker does with its update.
"""
import pytest

from dragonboat_tpu import Config, NodeHostConfig
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.node import Node
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.testing import CounterSM
from dragonboat_tpu.transport import ChanRouter, ChanTransport

from tests.loadwait import wait_until

CID = 4001
APPLIED = "dragonboat_node_offload_applied_total"

#: flagger -> (arguments but the term, which the live leader's is appended
#: to where ``TERM`` stands; the field it writes; the field's empty value;
#: the counter's ``kind``)
TERM = object()
FLAGGERS = {
    "offload_commit": ((1,), "_off_commit", 0, "commit"),
    "offload_election": ((True, TERM), "_off_election", None, "election"),
    "offload_read_confirm": ((71, 72, TERM), "_off_reads", [],
                             "read_confirm"),
    "offload_read_echo": ((2, 71, 72), "_off_read_echoes", [], "read_echo"),
    "offload_tick_elect": ((), "_off_elect", False, "tick"),
    "offload_tick_heartbeat": ((), "_off_hb", False, "tick"),
    "offload_tick_demote": ((), "_off_demote", False, "tick"),
    "offload_quiesce_enter": ((), "_off_quiesce", False, "tick"),
}


class Leader:
    """The group's node on its host, leading, with nothing queued."""

    def __init__(self):
        router = ChanRouter()
        self.nh = NodeHost(NodeHostConfig(
            node_host_dir=":memory:",
            rtt_millisecond=1_000_000,
            raft_address="gate1:1",
            raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                src, rh, ch, router=router),
            enable_metrics=True,
            expert=ExpertConfig(
                quorum_engine="tpu", engine_block_groups=8,
                engine_warm_fused=False),
        ))
        try:
            self.nh.start_cluster(
                {1: "gate1:1"}, False, CounterSM,
                Config(cluster_id=CID, node_id=1, election_rtt=10,
                       heartbeat_rtt=1))
            self.node = self.nh.get_node(CID)

            def leads():
                if self.nh.get_leader_id(CID) == (1, True):
                    return True
                self.node.request_campaign()
                return False

            wait_until(leads, timeout=60.0, interval=0.1, what="a leader")
            s = self.nh.get_noop_session(CID)
            self.nh.sync_propose(s, b"x", timeout=1e9)
            self.settle()
        except BaseException:
            self.nh.stop()
            raise

    def idle(self) -> bool:
        n, c = self.node, self.nh.quorum_coordinator
        return not (
            self.flagged() or n.commit_inflight or n._update_out
            or len(n.mq._left) + len(n.mq._right)
            or len(c._staged) or c._pending.is_set()
            or any(len(r) for r in self.nh.engine.step_ready.ready)
            or any(len(r) for r in self.nh.engine.apply_ready.ready)
        )

    def flagged(self) -> list:
        """The fields that hold an effect no turn has taken yet."""
        return [field for _a, field, empty, _k in FLAGGERS.values()
                if getattr(self.node, field) != empty]

    def settle(self) -> None:
        """Until the host's own threads have nothing left to do with the
        group: what follows is then the test's turn alone."""
        quiet = [0]

        def still():
            quiet[0] = quiet[0] + 1 if self.idle() else 0
            return quiet[0] >= 10

        wait_until(still, timeout=60.0, interval=0.01, what="an idle group")

    def flag(self, name: str) -> None:
        """Flag one effect as the round thread does under a host plane:
        no wake-up, so no worker but the test's turn takes it."""
        args = tuple(self.node.peer.raft.term if a is TERM else a
                     for a in FLAGGERS[name][0])
        getattr(self.node, name)(*args, wake=False)

    def turn(self) -> None:
        self.nh.engine.process_steps([self.node])

    def applied(self, kind: str) -> float:
        return self.nh.metrics_registry.counter_value(APPLIED, {"kind": kind})

    def stop(self) -> None:
        self.nh.stop()


@pytest.fixture(scope="module")
def the_leader():
    ld = Leader()
    yield ld
    ld.stop()


@pytest.fixture
def leader(the_leader):
    """A failed case leaves nothing flagged for the next one: a heartbeat
    flag opens every gate this file was ever run against."""
    yield the_leader
    the_leader.node.offload_tick_heartbeat(wake=False)
    the_leader.turn()


def test_the_table_names_every_flagger():
    """A ninth ``offload_*`` on ``Node`` has to be added above, where the
    tests below hold it to the gate."""
    assert sorted(FLAGGERS) == sorted(
        n for n in vars(Node) if n.startswith("offload_"))


@pytest.mark.parametrize("name", sorted(FLAGGERS))
def test_one_turn_takes_a_flagged_effect(leader, name):
    """``read_confirm`` and ``read_echo`` fail on the parent: its gate
    listed neither field."""
    _args, field, _empty, kind = FLAGGERS[name]
    leader.settle()
    before = leader.applied(kind)
    leader.flag(name)
    assert leader.flagged() == [field]
    leader.turn()
    assert leader.flagged() == []
    assert leader.applied(kind) == before + 1
    assert leader.nh.get_leader_id(CID) == (1, True)


@pytest.mark.parametrize("name", sorted(FLAGGERS))
def test_a_flag_raised_during_the_apply_opens_the_next_turn(
        leader, name, monkeypatch):
    """Between the swap and the return of ``_apply_offload_effects`` the
    round thread flags again: the turn under way must not clear it."""
    _args, field, _empty, kind = FLAGGERS[name]
    node = leader.node
    leader.settle()
    inner = node._catch_up_and_tick

    def flagging():  # runs after the swap: the tick flag below was taken
        leader.flag(name)
        inner()

    monkeypatch.setattr(node, "_catch_up_and_tick", flagging)
    node.offload_tick_heartbeat(wake=False)
    before = leader.applied(kind)
    leader.turn()
    monkeypatch.undo()
    assert leader.flagged() == [field]
    assert leader.applied(kind) == before + (kind == "tick")
    leader.turn()
    assert leader.flagged() == []
    assert leader.applied(kind) == before + (kind == "tick") + 1


def test_a_turn_with_nothing_flagged_does_not_apply(leader, monkeypatch):
    node = leader.node
    leader.settle()
    calls = []
    monkeypatch.setattr(
        node, "_apply_offload_effects", lambda: calls.append(1))
    leader.turn()
    assert calls == []
    node.offload_tick_heartbeat(wake=False)
    leader.turn()
    assert calls == [1]
    monkeypatch.undo()
    leader.turn()  # the real one takes what the recorder left flagged
    assert leader.flagged() == []
