"""The packed step against the unpacked entry points (ISSUES 30, 32).

The engine carries its state as two ``(rows, G)`` blocks, stages every
dispatch into ONE ingress block and reads ONE egress block back
(``ops/packed.py``).
The oracle here is the engine as it was before: ``_Unpacked`` stages a
dispatch the old way — separate padded arrays, a ``(max, touched)`` pair,
a ``bool`` echo cube, dummies for compiled-out planes — and calls the
kernels' own entry points (``kernels.quorum_step`` / ``quorum_step_dense``
/ ``quorum_multiround``: a ``QuorumState`` and separate arrays).  One
seeded random script goes through both; every state leaf, every
``StepResult`` / ``MultiRoundResult`` field and ``committed_view()`` must
be equal after every step.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from dragonboat_tpu.ops import kernels, packed  # noqa: E402
from dragonboat_tpu.ops.engine import (  # noqa: E402
    BatchedQuorumEngine,
    MultiRoundResult,
    _ack_columns,
    _concat_columns,
)
from dragonboat_tpu.ops.state import (  # noqa: E402
    VOTE_NONE,
    HostMirror,
    QuorumState,
    block_dims,
    pack_state,
    unpack_state,
)


class _Unpacked(BatchedQuorumEngine):
    """The three dispatch paths as they were before the blocks: the old
    staging, the unpacked entry points, six flag vectors."""

    def _finish(self, out):
        # the six flag vectors stay six, each under its ``StepResult``
        # name: the egress bit field's encode and decode are both the
        # packed side's alone (``_translate_egress`` below)
        self._flags = (
            ("won", out.won), ("lost", out.lost),
            ("elect", out.flags.elect_due), ("heartbeat", out.flags.hb_due),
            ("demote", out.flags.checkq_demote),
            ("quiesce", out.flags.quiesce_enter),
        )
        committed = np.asarray(out.committed)
        rows = [committed[None], np.zeros_like(committed)[None]]
        if out.read_done_count is not None:
            rows += [np.asarray(out.read_done_count).T,
                     np.asarray(out.read_done_index).T]
        if out.kv_read_index is not None:
            rows += [np.asarray(out.kv_read_val).T,
                     np.asarray(out.kv_read_index).T,
                     np.asarray(out.kv_applied)[None]]
        self._blk = packed.pack(out.state)
        return packed.PackedOut(
            self._blk, np.concatenate(rows, axis=0).astype(np.int32),
            out.telem,
        )

    def _translate_egress(self, res, committed, prev_committed, row_cid,
                          row_base, bits, flags=()):
        assert not bits.any()
        changed = super()._translate_egress(
            res, committed, prev_committed, row_cid, row_base, bits, flags
        )
        for name, arr in self._flags:  # as the engine did before the bits
            idx = np.nonzero(np.asarray(arr))[0]
            if idx.size:
                cids = row_cid[idx]
                getattr(res, name).extend(cids[cids >= 0].tolist())
        return changed

    def _pad(self, g, p, v, dtype):
        cap, n = self.event_cap, len(g)
        og, op = np.zeros((cap,), np.int32), np.zeros((cap,), np.int32)
        ov, valid = np.zeros((cap,), dtype), np.zeros((cap,), bool)
        og[:n], op[:n], ov[:n], valid[:n] = g, p, v, True
        return og, op, ov, valid

    def _dispatch(self, acks, votes, do_tick):
        ag, ap, av, avalid = self._pad(*acks, np.int32)
        if votes:
            cols = np.array(votes, dtype=np.int64).T
            vg, vp, vv, vvalid = self._pad(cols[0], cols[1], cols[2], np.int8)
        else:
            vg = vp = np.zeros((1,), np.int32)
            vv, vvalid = np.zeros((1,), np.int8), np.zeros((1,), bool)
        return self._finish(kernels.quorum_step(
            self.dev,
            *(jnp.asarray(a) for a in (ag, ap, av, avalid, vg, vp, vv, vvalid)),
            do_tick=do_tick, track_contact=self.device_ticks or do_tick,
            has_votes=bool(votes), has_quiesce=self._quiesce_used,
        ))

    def _read_arrays(self, lead, rounds):
        g, p, s = self.n_groups, self.n_peers, self.n_read_slots
        idx = np.full(lead + (g, s), -1, np.int32)
        cnt = np.zeros(lead + (g, s), np.int32)
        echo = np.zeros(lead + (g, s, p), bool)
        for r, (reads, racks) in enumerate(rounds):
            at = (r,) if lead else ()
            if reads is not None and reads[0].size:
                rr, sl, v, c = reads
                idx[at + (rr, sl)] = v
                cnt[at + (rr, sl)] = c
            if racks is not None and racks[0].size:
                rr, sl, pe = racks
                echo[at + (rr, sl, pe)] = True
        return tuple(jnp.asarray(a) for a in (idx, cnt, echo))

    def _kv_arrays(self, lead, rounds):
        g, e, rk = self.n_groups, self.n_kv_ents, self.n_kv_reads
        ei = np.full(lead + (g, e), -1, np.int32)
        ek = np.zeros(lead + (g, e), np.int32)
        ev = np.zeros(lead + (g, e), np.int32)
        rkey = np.full(lead + (g, rk), -1, np.int32)
        for r, (kvents, kvreads) in enumerate(rounds):
            at = (r,) if lead else ()
            if kvents is not None and kvents[0].size:
                rr, sl, rel, key, val = kvents
                ei[at + (rr, sl)] = rel
                ek[at + (rr, sl)] = key
                ev[at + (rr, sl)] = val
            if kvreads is not None and kvreads[0].size:
                rr, sl, key = kvreads
                rkey[at + (rr, sl)] = key
        return tuple(jnp.asarray(a) for a in (ei, ek, ev, rkey))

    def _dispatch_dense(self, acks, votes, do_tick, reads=None,
                        kvents=None, kvreads=None, has_kv=None):
        # the step hands over what it filtered, each event in the arity
        # it was staged in (ISSUE 35): flat columns for the kernel's
        # unpacked entry point
        ag, ap, av = _ack_columns(*acks)
        racks = None
        if reads is not None:
            stages, stage_blocks, echoes, echo_blocks = reads
            reads = _concat_columns(stages, stage_blocks, 4)
            racks = _concat_columns(echoes, echo_blocks, 3)
        g, p = self.n_groups, self.n_peers
        ack_max = np.zeros((g, p), np.int32)
        touched = np.zeros((g, p), bool)
        if ag.size:
            cell = ag.astype(np.int64) * p + ap
            np.maximum.at(ack_max.reshape(-1), cell, av)
            touched.reshape(-1)[cell] = True
        if votes:
            vote_new = np.full((g, p), VOTE_NONE, np.int8)
            cols = np.array(votes, dtype=np.int64).T
            vote_new[cols[0], cols[1]] = cols[2].astype(np.int8)
        else:
            vote_new = np.zeros((1, 1), np.int8)
        has_reads = reads is not None or racks is not None
        if has_kv is None:
            has_kv = kvents is not None or kvreads is not None
        return self._finish(kernels.quorum_step_dense(
            self.dev, jnp.asarray(ack_max), jnp.asarray(touched),
            jnp.asarray(vote_new),
            *(self._read_arrays((), [(reads, racks)]) if has_reads
              else (None,) * 3),
            *(self._kv_arrays((), [(kvents, kvreads)]) if has_kv
              else (None,) * 4),
            do_tick=do_tick, track_contact=self.device_ticks or do_tick,
            has_votes=bool(votes), has_reads=has_reads, has_kv=has_kv,
            has_quiesce=self._quiesce_used,
        ))

    def _dispatch_multiround(self, blocks, do_tick, tick_mask, k_rounds=None):
        k, g, p = len(blocks), self.n_groups, self.n_peers
        ack_max = np.full((k, g, p), -1, np.int32)
        for r, b in enumerate(blocks):
            if b.rows.size:
                cell = (r * g + b.rows.astype(np.int64)) * p + b.slots
                np.maximum.at(ack_max.reshape(-1), cell, b.rels)
        has_votes = any(b.votes for b in blocks)
        vote_new = np.zeros((1, 1, 1), np.int8)
        if has_votes:
            vote_new = np.full((k, g, p), VOTE_NONE, np.int8)
            for r, b in enumerate(blocks):
                if b.votes:
                    cols = np.array(b.votes, dtype=np.int64).T
                    vote_new[r, cols[0], cols[1]] = cols[2].astype(np.int8)
        has_churn = any(b.churn for b in blocks)
        churn = [np.zeros((1, 1), np.int32)] * 4
        if has_churn:
            cmax = max(len(b.churn) for b in blocks)
            cap = max(1 << max(0, cmax - 1).bit_length(), 1)
            churn = [np.full((k, cap), g, np.int32)] + [
                np.zeros((k, cap), np.int32) for _ in range(3)
            ]
            for r, b in enumerate(blocks):
                if b.churn:
                    cols = np.array(b.churn, dtype=np.int64).T
                    for dst, col in zip(churn, cols):
                        dst[r, :cols.shape[1]] = col
        has_reads = any(
            b.reads is not None or b.racks is not None for b in blocks
        )
        has_kv = any(
            b.kvents is not None or b.kvreads is not None for b in blocks
        ) or self._kv_ents_buffered()
        out = kernels.quorum_multiround(
            self.dev,
            *(jnp.asarray(a) for a in (ack_max, vote_new, *churn, tick_mask)),
            *(self._read_arrays((k,), [(b.reads, b.racks) for b in blocks])
              if has_reads else (None,) * 3),
            *(self._kv_arrays((k,), [(b.kvents, b.kvreads) for b in blocks])
              if has_kv else (None,) * 4),
            do_tick=do_tick, track_contact=self.device_ticks or do_tick,
            has_votes=has_votes, has_churn=has_churn, has_reads=has_reads,
            purge_reads=self._read_plane_used and has_churn, has_kv=has_kv,
            purge_kv=self._devsm_used and has_churn,
            purge_telem=False, has_quiesce=self._quiesce_used,
        )
        return self._finish(out), (has_reads, has_kv)


class _Script:
    """One seeded script, applied op for op to every engine handed in."""

    def __init__(self, engines, seed, g, p, reads, kv):
        self.engs = engines
        self.rng = np.random.default_rng(seed)
        self.p, self.reads, self.kv = p, reads, kv
        self.n = min(g - 4, 40)  # registered groups; spare rows stay dead
        self.term = {}
        self.last = {}
        self.role = {}
        self.members = {}
        self.kv_next = {}
        self.sleepers = []
        for cid in range(1, self.n + 1):
            ids = list(range(1, (3 if cid % 2 or p < 5 else 5) + 1))
            self.members[cid] = ids
            self.term[cid], self.last[cid] = 1, 1
            self.kv_next[cid] = 2
            # on the wide engines (the last peer slot free) every 4th
            # three-member group can sleep: idle for two ticks, it goes
            # (``quiesce``), and the marks below wake and put to sleep
            sleepy = p >= 5 and len(ids) == 3 and cid % 4 == 1
            for e in engines:
                # every 7th group's leader sits under CheckQuorum (its
                # window closes on the third tick: ``demote``); a follower
                # nobody contacts times out on every second (``elect``)
                e.add_group(cid, node_ids=ids, self_id=1,
                            election_timeout=3 if cid % 7 == 0 else 6,
                            heartbeat_timeout=2, check_quorum=cid % 7 == 0,
                            rand_timeout=2 if cid % 10 == 0 else 6,
                            **({"quiesce_threshold": 2} if sleepy
                               else {}))
            if sleepy:
                self.sleepers.append(cid)
            if cid % 5 == 0:
                self.role[cid] = "follower"
                self.each("set_follower", cid, 1)
            else:
                self.role[cid] = "leader"
                self.each("set_leader", cid, term=1, term_start=1,
                          last_index=1)

    def each(self, name, *a, **k):
        got = [getattr(e, name)(*a, **k) for e in self.engs]
        assert all(x == got[0] for x in got), (name, got)
        return got[0]

    def leaders(self):
        return [c for c, r in self.role.items() if r == "leader"]

    def events(self):
        """One round's worth of staged events."""
        rng = self.rng
        lead = self.leaders()
        for cid in rng.choice(lead, size=min(len(lead), 10), replace=False):
            cid = int(cid)
            self.last[cid] += int(rng.integers(1, 4))
            self.each("ack", cid, 1, self.last[cid])
            for nid in self.members[cid][1:]:
                if rng.random() < 0.7:
                    self.each("ack", cid, nid,
                              self.last[cid] - int(rng.integers(0, 2)))
        # a block of acks, heartbeat responses and contacts
        e0 = self.engs[0]
        blk = [int(c) for c in rng.choice(lead, size=min(len(lead), 6),
                                          replace=False)]
        rows = np.array([e0.groups[c].row for c in blk], np.int32)
        slots = np.array([e0.groups[c].slots[2] for c in blk], np.int32)
        rels = np.array([self.last[c] - e0.groups[c].base for c in blk],
                        np.int32)
        self.each_block("ack_block", rows, slots, rels)
        self.each_block("heartbeat_resp_block", rows[:3], slots[:3])
        for cid, role in self.role.items():
            if role == "follower" and cid % 10 and rng.random() < 0.5:
                self.each("leader_contact", cid)
            if role == "candidate":
                # the first campaigners hear from every peer, and every
                # other one of them is refused: ``won`` and ``lost``
                sure = cid % 10 == 5
                for nid in self.members[cid][1:]:
                    if sure or rng.random() < 0.6:
                        self.each("vote", cid, nid,
                                  cid % 20 != 5 if sure
                                  else bool(rng.random() < 0.7))
        cid = int(rng.choice(lead))
        self.each("heartbeat_resp", cid, 3)
        for cid in self.sleepers:  # a group's sleep / wake marks
            if cid in e0.groups and rng.random() < 0.3:
                self.each("quiesce_mark", cid, bool(rng.random() < 0.6))
        if self.reads:
            for cid in rng.choice(lead, size=4, replace=False):
                cid = int(cid)
                if not self.each("read_slots_free", cid):
                    continue
                slot = self.each("stage_read", cid,
                                 count=int(rng.integers(1, 5)))
                roll = rng.random()
                if roll < 0.15:
                    self.each("cancel_read", cid, slot)
                elif roll < 0.85:
                    for nid in self.members[cid][1:]:
                        if rng.random() < 0.8:
                            self.each("read_ack", cid, nid, slot)
        if self.kv:
            for cid in rng.choice(lead, size=3, replace=False):
                cid = int(cid)
                idx = self.kv_next[cid] = max(self.kv_next[cid],
                                              self.last[cid]) + 1
                self.last[cid] = idx
                self.each("stage_kv_ops", cid, [idx],
                          [int(rng.integers(0, 16))],
                          [int(rng.integers(1, 1000))])
                self.each("ack", cid, 1, idx)
                self.each("ack", cid, 2, idx)
                if rng.random() < 0.5 and self.each("kv_reads_free", cid):
                    self.each("stage_kv_read", cid, int(rng.integers(0, 16)))

    def each_block(self, name, *arrays):
        for e in self.engs:
            getattr(e, name)(*(a.copy() for a in arrays))

    def transitions(self, step):
        """The rare path: a campaign, its outcome, a leader stepping
        down, between steps."""
        rng = self.rng
        for cid, role in list(self.role.items()):
            if role == "follower" and cid % 10 and (
                rng.random() < 0.3 or (step == 0 and cid % 10 == 5)
            ):
                self.term[cid] += 1
                self.role[cid] = "candidate"
                self.each("set_candidate", cid, self.term[cid])
            elif role == "candidate" and rng.random() < 0.5:
                self.role[cid] = "leader"
                self.last[cid] += 1
                self.each("set_leader", cid, term=self.term[cid],
                          term_start=self.last[cid],
                          last_index=self.last[cid])
        if step % 3 == 2:
            cid = int(rng.choice(self.leaders()))
            self.term[cid] += 1
            self.role[cid] = "follower"
            self.each("set_follower", cid, self.term[cid])

    def recycle(self):
        """A same-geometry tenant swap, in-program (forces the fused
        path): a leader group leaves, a fresh one takes its row."""
        old = next(c for c in self.leaders() if len(self.members[c]) == 3)
        new = 1000 + old
        self.each("stage_recycle", old, new, term=1, term_start=1,
                  last_index=1)
        for d in (self.term, self.last, self.role, self.members,
                  self.kv_next):
            d[new] = d.pop(old)
        self.term[new], self.last[new], self.kv_next[new] = 1, 1, 2


def _assert_state_equal(a, b, where):
    for name, x, y in zip(QuorumState._fields, a.dev, b.dev):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape, (where, name)
        assert np.array_equal(x, y), (where, name)


def _assert_result_equal(ra, rb, where):
    assert type(ra) is type(rb), where
    assert ra.commit == rb.commit, where
    for f in ("won", "lost", "elect", "heartbeat", "demote"):
        assert sorted(getattr(ra, f)) == sorted(getattr(rb, f)), (where, f)
    assert ra.reads == rb.reads, where
    assert ra.kv_reads == rb.kv_reads, where
    assert ra.kv_applied_ops == rb.kv_applied_ops, where
    if isinstance(ra, MultiRoundResult):
        assert ra.rounds == rb.rounds, where
        assert np.array_equal(ra.committed_rel, rb.committed_rel), where
        assert np.array_equal(ra.commit_rows, rb.commit_rows), where


CASES = [
    (mode, g, p, reads)
    for mode in ("sparse", "dense", "fused4", "fused16")
    for g, p in ((64, 3), (1024, 5))
    for reads in (False, True)
]


@pytest.mark.parametrize("mode,g,p,reads", CASES)
def test_packed_step_equals_unpacked_entry_points(mode, g, p, reads):
    kv = reads and p == 5  # the devsm plane rides the cases it can
    kw = dict(
        event_cap=256,
        dense_ingest={"sparse": False, "dense": True}.get(mode, "auto"),
    )
    a = BatchedQuorumEngine(g, p, **kw)
    b = _Unpacked(g, p, **kw)
    if p >= 5:  # a group's sleep rides the engines wide enough for it
        a.enable_quiesce()
        b.enable_quiesce()
    script = _Script([a, b], seed=30 + g + p, g=g, p=p, reads=reads, kv=kv)
    k = int(mode[5:]) if mode.startswith("fused") else 0
    seen = dict(commits=0, reads=0, kv=0, won=0, lost=0, elect=0,
                heartbeat=0, demote=0, quiesce=0)
    for step in range(7):
        where = f"{mode} g={g} p={p} reads={reads} step={step}"
        do_tick = step % 2 == 0
        if k:
            rounds = 2 + step % 3
            for _ in range(rounds):
                script.events()
                script.each_block("begin_round")
            if step == 3:
                script.recycle()
            ra, rb = (
                e.step_rounds(do_tick=do_tick, pad_rounds_to=k,
                              tick_rounds=min(k, rounds + step % 2))
                for e in (a, b)
            )
        else:
            script.events()
            if step == 3:
                script.recycle()  # reroutes this step to the fused path
            ra, rb = (e.step(do_tick=do_tick) for e in (a, b))
        _assert_result_equal(ra, rb, where)
        seen["commits"] += len(ra.commit)
        seen["reads"] += sum(r[3] for r in ra.reads)
        seen["kv"] += ra.kv_applied_ops + len(ra.kv_reads)
        for f in packed.FLAG_BITS:
            seen[f] += len(getattr(ra, f))
        assert np.array_equal(a.committed_view(), b.committed_view()), where
        _assert_state_equal(a, b, where)
        if kv:
            cid = script.leaders()[0]
            assert np.array_equal(a.kv_values(cid), b.kv_values(cid)), where
        script.transitions(step)
    # the script reached every egress field it compares
    assert seen["commits"] and all(
        seen[f] for f in packed.FLAG_BITS if f != "quiesce" or p >= 5), seen
    assert bool(seen["reads"]) == reads and bool(seen["kv"]) == kv, seen


@pytest.mark.parametrize("g,p,dims", [
    (8, 3, (4, 16, 16)), (64, 5, (4, 16, 16)), (16, 4, (4, 5, 7)),
    (16, 7, (2, 16, 3)),
])
def test_pack_unpack_round_trip_every_leaf(g, p, dims):
    rng = np.random.default_rng(g * p)
    m = HostMirror(g, p, *dims)
    for arr in m.arrays.values():
        if arr.dtype == bool:
            arr[...] = rng.integers(0, 2, arr.shape).astype(bool)
        else:
            info = np.iinfo(arr.dtype)
            arr[...] = rng.integers(info.min, info.max, arr.shape,
                                    dtype=arr.dtype, endpoint=True)
    st = QuorumState(**m.arrays)
    host = pack_state(st, np)
    dev = packed.pack(m.to_device())
    # two blocks, one a storage dtype, the group axis last in both
    assert [(b.ndim, b.shape[1], b.dtype) for b in host] == [
        (2, g, np.int32), (2, g, np.int8)]
    assert block_dims(host, dims) == (g, p)
    for hb, db in zip(host, dev):  # the host's and the program's agree
        assert hb.dtype == db.dtype and np.array_equal(hb, np.asarray(db))
    for name, x, y, z in zip(
        QuorumState._fields, st, unpack_state(host, dims, np),
        packed.unpack(dev, dims=dims),
    ):
        for got in (y, np.asarray(z)):
            assert got.dtype == x.dtype and got.shape == x.shape, name
            assert np.array_equal(got, x), name
    again = pack_state(unpack_state(host, dims, np), np)
    for hb, ab in zip(host, again):  # pack(unpack(x)) == x
        assert np.array_equal(hb, ab)


@pytest.mark.parametrize("mode", ["sparse", "dense", "fused4"])
def test_packed_step_on_a_group_sharded_engine(mode):
    """The same script on an engine whose blocks shard their LAST axis
    (the groups) over the virtual CPU devices: equal to the unsharded
    unpacked oracle step for step, and still sharded after every one."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dragonboat_tpu.ops.sharding import GROUP_AXIS, make_mesh

    devices = jax.local_devices(backend="cpu")
    n_dev = min(4, len(devices))
    if n_dev < 2:
        pytest.skip("one CPU device: nothing to shard over")
    g, p = 64, 3
    kw = dict(
        event_cap=256,
        dense_ingest={"sparse": False, "dense": True}.get(mode, "auto"),
    )
    a = BatchedQuorumEngine(g, p, sharding=NamedSharding(
        make_mesh(np.array(devices[:n_dev])), P(GROUP_AXIS)), **kw)
    b = _Unpacked(g, p, **kw)
    script = _Script([a, b], seed=32, g=g, p=p, reads=mode != "sparse",
                     kv=False)
    for step in range(5):
        where = f"sharded {mode} step={step}"
        script.events()
        if mode == "fused4":
            script.each_block("begin_round")
            script.events()
            ra, rb = (e.step_rounds(do_tick=step % 2 == 0, pad_rounds_to=4)
                      for e in (a, b))
        else:
            ra, rb = (e.step(do_tick=step % 2 == 0) for e in (a, b))
        _assert_result_equal(ra, rb, where)
        assert np.array_equal(a.committed_view(), b.committed_view()), where
        _assert_state_equal(a, b, where)
        for blk in a._blk:
            assert blk.sharding.spec == P(None, GROUP_AXIS), where
        script.transitions(step)
