"""The ack path's staging against the one it replaced (ISSUE 32).

Between an ack's drain and its launch the engine reads no array through an
index array any more: tuple-staged acks are filtered as tuples, a staged
block carries the engine's epoch generation and is compared row by row
only if a row transition came after it, a contact block gets its slots
from the groups the coordinator walks anyway.  The oracle is the staging
as it was: ``_OldStaging`` keeps the old ``ack_block`` /
``leader_contact_block`` / ``_gather_acks`` (a per-row epoch copy on every
block, index-array reads throughout).  One seeded script goes through
both; every launch of both must be handed a byte-identical ingress
buffer, and ``ack_blocks_stale`` must count exactly the blocks staged
before a bump.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dragonboat_tpu.events import MetricsRegistry  # noqa: E402
from dragonboat_tpu.obs import FlightRecorder  # noqa: E402
from dragonboat_tpu.ops.engine import BatchedQuorumEngine  # noqa: E402

CAP = 24
G, P = 16, 3


class _OldStaging(BatchedQuorumEngine):
    """``ack_block``, ``leader_contact_block`` and ``_gather_acks`` as
    they were before ISSUE 32 (``_filter_acks`` hands the old gather to
    the step)."""

    def ack_block(self, rows, slots, rels) -> None:
        rows = np.asarray(rows)
        slots = np.asarray(slots)
        rels = np.asarray(rels)
        rels = np.maximum(rels, 0)
        rows32 = rows.astype(np.int32)
        self._ack_blocks.append(
            (rows32, slots.astype(np.int32), rels.astype(np.int32),
             self._row_epoch[rows32].copy())
        )

    def leader_contact_block(self, rows) -> None:
        rows = np.asarray(rows, dtype=np.int32)
        self.ack_block(
            rows, self.mirror.arrays["self_slot"][rows],
            np.zeros(rows.shape, np.int32),
        )

    def _gather_acks(self):
        parts = []
        if self._acks:
            cols = np.array(self._acks, dtype=np.int64)
            rows = cols[:, 0].astype(np.int32)
            keep = cols[:, 3].astype(np.int32) == self._row_epoch[rows]
            parts.append(
                (rows[keep], cols[keep, 1].astype(np.int32),
                 cols[keep, 2].astype(np.int32))
            )
            self._acks = []
        if self._ack_blocks:
            for r, s, v, ep in self._ack_blocks:
                keep = ep == self._row_epoch[r]
                if keep.all():
                    parts.append((r, s, v))
                elif keep.any():
                    parts.append((r[keep], s[keep], v[keep]))
            self._ack_blocks = []
        if not parts:
            z = np.zeros((0,), np.int32)
            return z, z, z
        return (
            np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]),
            np.concatenate([p[2] for p in parts]),
        )

    def _filter_acks(self):
        # the step's seam since ISSUE 35: the old gather's arrays, handed
        # on as one block, reach the sparse lists and the dense plane
        # through index arrays as they did
        cols = self._gather_acks()
        return (), ([cols] if cols[0].size else [])


def _capture_launches(eng):
    """Every launch's program, statics and ingress bytes, as handed over."""
    seen = []
    launch = eng._launch

    def capturing(fn, ing, **statics):
        seen.append((fn, tuple(sorted(statics.items())), ing.buf.copy()))
        return launch(fn, ing, **statics)

    eng._launch = capturing
    return seen


class _Pair:
    """The new engine and the old staging, driven op for op."""

    def __init__(self, dense_ingest, seed):
        kw = dict(event_cap=CAP, dense_ingest=dense_ingest)
        self.new = BatchedQuorumEngine(G, P, **kw)
        self.old = _OldStaging(G, P, **kw)
        self.rec = FlightRecorder(stall_ms=0)
        self.new.enable_obs(recorder=self.rec, registry=MetricsRegistry())
        self.rng = np.random.default_rng(seed)
        self.term = {}
        self.last = {}
        for cid in range(1, G - 1):  # two rows stay dead
            # this replica is not always peer slot 0
            self.each("add_group", cid, node_ids=[1, 2, 3],
                      self_id=1 + cid % 3)
            self.term[cid], self.last[cid] = 1, 1
            if cid % 4:
                self.each("set_leader", cid, term=1, term_start=1,
                          last_index=1)
            else:
                self.each("set_follower", cid, 1)
        # commits, then a rebase: acks below the new base are legal
        for cid in (1, 2, 3):
            for nid in (1, 2, 3):
                self.each("ack", cid, nid, 9)
            self.last[cid] = 9
        self.each("step", do_tick=False)
        for cid in (1, 2):
            self.each("rebase", cid)
            assert self.new.groups[cid].base > 1
        self.each("step", do_tick=False)
        self.launches = [_capture_launches(e) for e in (self.new, self.old)]

    def each(self, name, *a, **k):
        return [getattr(e, name)(*a, **k) for e in (self.new, self.old)]

    def leaders(self):
        return [c for c in self.term if c % 4]

    def followers(self):
        return [c for c in self.term if not c % 4]

    def tuple_acks(self, n):
        """``n`` tuple-staged events: acks (some below the base, some on
        rows that are about to transition or just did), heartbeat
        responses, contacts."""
        rng = self.rng
        for _ in range(n):
            roll = rng.random()
            if roll < 0.6:
                cid = int(rng.choice(self.leaders()))
                self.last[cid] += int(rng.integers(0, 3))
                # index 1 lies below the base of the rebased groups
                idx = 1 if roll < 0.1 else self.last[cid]
                self.each("ack", cid, int(rng.integers(1, 4)), idx)
            elif roll < 0.8:
                self.each("heartbeat_resp", int(rng.choice(self.leaders())),
                          int(rng.integers(1, 4)))
            else:
                self.each("leader_contact", int(rng.choice(self.followers())))

    def contact_block(self, n):
        cids = [int(c) for c in self.rng.choice(
            self.followers(), size=min(n, len(self.followers())),
            replace=False)]
        gi = self.new.groups
        rows = [gi[c].row for c in cids]
        # the coordinator's walk: each row's own slot off its group
        self.new.heartbeat_resp_block(rows, [gi[c].self_slot for c in cids])
        self.old.leader_contact_block(rows)
        return len(cids)

    def resp_block(self, n):
        cids = [int(c) for c in self.rng.choice(self.leaders(), size=n)]
        gi = self.new.groups
        rows = [gi[c].row for c in cids]
        slots = [int(self.rng.integers(0, 3)) for _ in cids]
        self.each("heartbeat_resp_block", rows, slots)
        return n

    def transition(self):
        """A row's epoch is bumped: a follower campaigns (and hears its
        votes), a leader steps down and is elected again."""
        rng = self.rng
        cid = int(rng.choice(self.followers()))
        self.term[cid] += 1
        self.each("set_candidate", cid, self.term[cid])
        for nid in (1, 2, 3):
            self.each("vote", cid, nid, bool(rng.random() < 0.7))
        self.each("vote", cid, 2, True)  # a duplicate: first wins
        cid = int(rng.choice(self.leaders()))
        self.term[cid] += 1
        self.each("set_follower", cid, self.term[cid])
        self.last[cid] += 1
        self.each("set_leader", cid, term=self.term[cid],
                  term_start=self.last[cid], last_index=self.last[cid])

    def round(self, n):
        """One round of ``n`` ack events, blocks and tuples on both sides
        of a transition where ``n`` has room; returns how many blocks were
        staged before the bump."""
        before = 0
        left = n
        if n >= 5:
            left -= self.contact_block(2)
            left -= self.resp_block(1)
            before = 2
        self.tuple_acks(left // 2)
        left -= left // 2
        self.transition()
        if n >= 5:
            left -= self.resp_block(1)  # after the bump: whole
        self.tuple_acks(left)
        return before


def _same_launches(pair, where):
    new, old = pair.launches
    assert len(new) == len(old) and new, where
    for (fn_a, st_a, buf_a), (fn_b, st_b, buf_b) in zip(new, old):
        assert fn_a is fn_b and st_a == st_b, where
        assert buf_a.dtype == buf_b.dtype and buf_a.shape == buf_b.shape
        assert buf_a.tobytes() == buf_b.tobytes(), where
    new.clear()
    old.clear()


@pytest.mark.parametrize("n", [0, 1, 5, CAP, CAP + 1])
@pytest.mark.parametrize("mode", ["sparse", "auto", "rounds"])
def test_ingress_is_byte_identical_to_the_old_staging(mode, n):
    pair = _Pair(False if mode == "sparse" else "auto", seed=32 + n)
    stale = []
    for step in range(6):
        where = f"{mode} n={n} step={step}"
        before = pair.round(n)
        if mode == "rounds":
            pair.each("begin_round")
            before += pair.round(n)
            ra, rb = pair.each("step_rounds", do_tick=step % 2 == 0,
                               pad_rounds_to=4)
        else:
            ra, rb = pair.each("step", do_tick=step % 2 == 0)
        stale.append(before)
        _same_launches(pair, where)
        assert ra.commit == rb.commit, where
        for f in ("won", "lost", "elect", "heartbeat", "demote"):
            assert sorted(getattr(ra, f)) == sorted(getattr(rb, f)), where
        assert np.array_equal(
            pair.new.committed_view(), pair.old.committed_view()), where
    spans = [s for s in pair.rec.spans()
             if s["kind"] in ("dispatch", "fused")][-6:]
    # exactly the blocks staged before a bump took the per-row comparison
    assert [s["ack_blocks_stale"] for s in spans] == stale
    assert bool(sum(stale)) == (n >= 5)
    # no staged block is left to be older: the bump list is short-lived
    assert pair.new._epoch_bumped == [] and not pair.new._ack_blocks


def test_bumps_with_no_block_staged_are_not_kept():
    """The rows bumped are remembered only while a staged block could be
    older than them: an engine that transitions and never stages a block
    (a bench driving ``ack_block_rounds``) keeps an empty list."""
    eng = BatchedQuorumEngine(G, P, event_cap=CAP)
    for cid in range(1, 9):
        eng.add_group(cid, node_ids=[1, 2, 3], self_id=1)
        eng.set_leader(cid, term=1, term_start=1, last_index=1)
    assert eng._epoch_gen == 8 and eng._epoch_bumped == []
    eng.heartbeat_resp_block([0, 1], [1, 1])
    eng.set_follower(1, 2)
    assert eng._epoch_bumped == [eng.groups[1].row]
    eng.step(do_tick=False)
    assert eng._epoch_bumped == []


def test_a_contact_block_lands_on_each_rows_own_slot():
    """``GroupInfo.self_slot`` is the mirror's ``self_slot`` column, at
    registration and for the tenant an in-program recycle puts on the
    row: what the coordinator's contact blocks are staged on."""
    eng = BatchedQuorumEngine(G, P, event_cap=CAP)
    for cid in range(1, 7):
        eng.add_group(cid, node_ids=[1, 2, 3], self_id=1 + cid % 3)
        eng.set_leader(cid, term=1, term_start=1, last_index=1)
    eng.stage_recycle(3, 103, term=1, term_start=1, last_index=1)
    for cid, gi in eng.groups.items():
        assert gi.self_slot == eng.mirror.arrays["self_slot"][gi.row], cid
    assert {gi.self_slot for gi in eng.groups.values()} == {0, 1, 2}
