"""The read plane's staging and decode against the ones they replaced
(ISSUE 35).

Between the coordinator's drain and its fan-out a step visits only the
(row, slot) pairs its round's staged events name: tuple-staged read stages,
cancels, echoes (and acks) are filtered as tuples and stored by scalar
stores, the ingress reset puts back those cells alone, and the decode reads
``done_cnt[r, s]`` for the candidate pairs.  Block-staged events stay
vectorized and carry the engine's epoch generation.  The oracle is the step
as it was: ``_OldReadStaging`` keeps the old ``stage_read_block`` /
``read_ack_block`` (a per-row epoch copy on every block), ``_gather_reads``
(index-array filters), dense read staging (index-array stores after a whole
refill) and ``_translate_reads`` (a scan of the whole plane).  One seeded
script goes through both; every launch of both must be handed a
byte-identical ingress buffer, every ``StepResult`` must hold equal arrays,
and the span fields must count exactly what took each path.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dragonboat_tpu.events import MetricsRegistry  # noqa: E402
from dragonboat_tpu.obs import FlightRecorder  # noqa: E402
from dragonboat_tpu.ops import packed as _pk  # noqa: E402
from dragonboat_tpu.ops.engine import (  # noqa: E402
    BatchedQuorumEngine, _ack_columns,
)
from tests.test_ack_staging import (  # noqa: E402
    _capture_launches, _same_launches,
)

G, P = 16, 8
FLAGS = ("won", "lost", "elect", "heartbeat", "demote")
READ_ARRAYS = ("read_cids", "read_slots", "read_index_abs", "read_counts")


class _OldReadStaging(BatchedQuorumEngine):
    """The read plane's staging, gather, dense stores and decode as they
    were before ISSUE 35, behind the step's seams of today."""

    def stage_read_block(self, rows, rels, counts) -> np.ndarray:
        rows = np.asarray(rows)
        rels = np.asarray(rels)
        counts = np.asarray(counts)
        rows64 = rows.astype(np.int64)
        slot = self._free_read_slot(rows64)
        if (slot < 0).any():
            raise RuntimeError("no free pending-read slot")
        self._read_plane_used = True
        self._read_busy[rows64, slot] = True
        self._read_next_slot[rows64] = (slot + 1) % self.n_read_slots
        self._read_echo_host[rows64, slot, :] = False
        self._read_stage_blocks.append(
            (rows.astype(np.int32), slot.astype(np.int32),
             rels.astype(np.int32), counts.astype(np.int32),
             self._row_epoch[rows.astype(np.int32)].copy())
        )
        return slot

    def read_ack_block(self, rows, rslots, peers) -> None:
        rows = np.asarray(rows)
        rslots = np.asarray(rslots)
        peers = np.asarray(peers)
        rows32 = rows.astype(np.int32)
        self._read_plane_used = True
        self._read_echo_blocks.append(
            (rows32, rslots.astype(np.int32), peers.astype(np.int32),
             self._row_epoch[rows32].copy())
        )
        rows64 = rows.astype(np.int64)
        rslots64 = rslots.astype(np.int64)
        self._read_echo_host[rows64, rslots64, peers.astype(np.int64)] = True
        self._predict_read_confirm(rows64, rslots64)

    def _gather_reads(self):
        self._round_seq += 1
        reads = racks = None
        parts = []
        if self._read_stages:
            cols = np.array(self._read_stages, dtype=np.int64)
            rows = cols[:, 0].astype(np.int32)
            keep = cols[:, 4].astype(np.int32) == self._row_epoch[rows]
            if keep.any():
                parts.append(tuple(
                    cols[keep, i].astype(np.int32) for i in range(4)
                ))
            self._read_stages = []
        if self._read_stage_blocks:
            for r, sl, v, c, ep in self._read_stage_blocks:
                keep = ep == self._row_epoch[r]
                if keep.all():
                    parts.append((r, sl, v, c))
                elif keep.any():
                    parts.append((r[keep], sl[keep], v[keep], c[keep]))
            self._read_stage_blocks = []
        if parts:
            reads = tuple(
                np.concatenate([p[i] for p in parts]) for i in range(4)
            )
        parts = []
        if self._read_echoes:
            cols = np.array(self._read_echoes, dtype=np.int64)
            rows = cols[:, 0].astype(np.int32)
            keep = cols[:, 3].astype(np.int32) == self._row_epoch[rows]
            if keep.any():
                parts.append(tuple(
                    cols[keep, i].astype(np.int32) for i in range(3)
                ))
            self._read_echoes = []
        if self._read_echo_blocks:
            for r, sl, p, ep in self._read_echo_blocks:
                keep = ep == self._row_epoch[r]
                if keep.all():
                    parts.append((r, sl, p))
                elif keep.any():
                    parts.append((r[keep], sl[keep], p[keep]))
            self._read_echo_blocks = []
        if parts:
            racks = tuple(
                np.concatenate([p[i] for p in parts]) for i in range(3)
            )
        self._epoch_bumped.clear()
        return reads, racks

    def _filter_reads(self):
        # the step's seam: the old gather's arrays, handed on as they are
        return self._gather_reads()

    def _dispatch_dense(self, acks, votes, do_tick, reads=None,
                        kvents=None, kvreads=None, has_kv=None):
        assert kvents is None and kvreads is None and not has_kv
        ag, ap, av = _ack_columns(*acks)
        reads, racks = reads if reads is not None else (None, None)
        p = self.n_peers
        has_votes = bool(votes)
        has_reads = reads is not None or racks is not None
        # the reset refills every section whole: no cells are named
        ing = self._ingress_for(
            "dense", has_votes=has_votes, has_reads=has_reads, has_kv=False,
        )
        v = ing.views
        if ag.size:
            np.maximum.at(
                v["ack"].reshape(-1), ag.astype(np.int64) * p + ap, av
            )
        if has_votes:
            cols = np.array(votes, dtype=np.int64).T
            v["votes"][cols[0], cols[1]] = cols[2]
        if reads is not None and reads[0].size:
            rr, sl, val, c = reads
            v["read_idx"][rr, sl] = val
            v["read_cnt"][rr, sl] = c
        if racks is not None and racks[0].size:
            rr, sl, pe = racks
            np.bitwise_or.at(
                v["read_echo"], (rr, sl), np.left_shift(1, pe)
            )
        return self._launch(
            _pk.quorum_step_dense, ing, do_tick=do_tick,
            track_contact=self.device_ticks or do_tick,
            has_votes=has_votes, has_reads=has_reads, has_kv=False,
        )

    def _decode_reads(self, res, done_cnt, done_idx, reads) -> int:
        self._translate_reads(
            res, done_cnt, done_idx, self._row_cid, self._row_base
        )
        return 0


def _scan_beside_the_decode(eng, mismatches):
    """Hold the candidate decode equal to a scan of the whole plane, on
    every dispatch of the new engine itself."""
    decode = eng._decode_reads

    def both(res, done_cnt, done_idx, reads):
        n = decode(res, done_cnt, done_idx, reads)
        scan = type(res)()
        eng._translate_reads(
            scan, done_cnt, done_idx, eng._row_cid, eng._row_base
        )
        if res.reads != scan.reads:
            mismatches.append((res.reads, scan.reads))
        return n

    eng._decode_reads = both


# the groups: five voters of the eight peer slots (this replica not always
# slot 0), one with a quorum of one, one with an observer, followers
SOLO, OBSERVED = 12, 13


class _Pair:
    """The new engine and the old staging, driven op for op."""

    def __init__(self, arity, seed):
        self.arity = arity
        self.new = BatchedQuorumEngine(G, P)
        self.old = _OldReadStaging(G, P)
        self.rec = FlightRecorder(stall_ms=0)
        self.new.enable_obs(recorder=self.rec, registry=MetricsRegistry())
        self.rng = np.random.default_rng(seed)
        self.term, self.last, self.nodes = {}, {}, {}
        for cid in range(1, G - 1):  # two rows stay dead
            if cid == SOLO:
                nodes, kw = [1], {}
            elif cid == OBSERVED:
                nodes, kw = [1, 2, 3], {"observers": (4,)}
            else:
                nodes, kw = [1, 2, 3, 4, 5], {}
            self.nodes[cid] = nodes + list(kw.get("observers", ()))
            self.each("add_group", cid, node_ids=nodes,
                      self_id=nodes[cid % len(nodes)], **kw)
            self.term[cid], self.last[cid] = 1, 1
            if cid % 5:
                self.each("set_leader", cid, term=1, term_start=1,
                          last_index=1)
            else:
                self.each("set_follower", cid, 1)
        for cid in (1, 2, 3):  # commits, then a rebase
            for nid in self.nodes[cid]:
                self.each("ack", cid, nid, 9)
            self.last[cid] = 9
        self.each("step", do_tick=False)
        self.each("rebase", 2)
        self.each("step", do_tick=False)
        self.launches = [_capture_launches(e) for e in (self.new, self.old)]
        self.mismatches = []
        _scan_beside_the_decode(self.new, self.mismatches)
        self.open = {}      # cid -> {slot: round staged}: what may be cancelled
        self.rnd = 0
        self.new_round()
        self.did = set()

    def new_round(self):
        # what the round's span must count, kept by the driver itself
        self.tuples = []    # (kind, row, slot, epoch at staging)
        self.blocks = []    # (rows of a staged block, generation at staging)

    def each(self, name, *a, **k):
        out = []
        for e in (self.new, self.old):
            try:
                out.append(getattr(e, name)(*a, **k))
            except RuntimeError as err:  # a refused stage, on both
                out.append(err)
        return out

    def leaders(self):
        return [c for c in self.term if c % 5]

    def _row(self, cid):
        return self.new.groups[cid].row

    def _note(self, kind, cid, slot):
        row = self._row(cid)
        self.tuples.append((kind, row, slot, int(self.new._row_epoch[row])))

    # -- tuple arity -----------------------------------------------------
    def stage(self, cid):
        idx = self.new.committed_index(cid)
        assert idx == self.old.committed_index(cid)
        a, b = self.each("stage_read", cid, count=1 + cid % 3, index=idx)
        if isinstance(a, RuntimeError):
            assert isinstance(b, RuntimeError)
            self.did.add("overflow")
            return
        assert a == b
        self._note("stage", cid, a)
        self.open.setdefault(cid, {})[a] = self.rnd

    def echo(self, cid, slot=None):
        slot = int(self.rng.integers(0, 4)) if slot is None else slot
        nid = int(self.rng.choice(self.nodes[cid][1:] or self.nodes[cid]))
        self.each("read_ack", cid, nid, slot)
        self._note("echo", cid, slot)

    def cancel(self):
        old = [(c, sl) for c, d in self.open.items()
               for sl, r in d.items() if r < self.rnd]
        if old:
            cid, sl = old[int(self.rng.integers(0, len(old)))]
            self.each("cancel_read", cid, sl)
            self._note("stage", cid, sl)
            del self.open[cid][sl]
            self.did.add("cancel")

    def acks(self, n):
        for _ in range(n):
            cid = int(self.rng.choice(self.leaders()))
            self.last[cid] += int(self.rng.integers(0, 2))
            self.each("ack", cid, int(self.rng.choice(self.nodes[cid])),
                      self.last[cid])

    # -- block arity -----------------------------------------------------
    def stage_block(self, n):
        cids = [c for c in self.rng.permutation(self.leaders())[:n]
                if self.new.read_slots_free(int(c)) > 0]
        if not cids:
            return
        cids = [int(c) for c in cids]
        rows = np.array([self._row(c) for c in cids], np.int32)
        rels = np.array(
            [self.new._rel(self.new.groups[c], self.new.committed_index(c))
             for c in cids], np.int32)
        counts = np.array([1 + c % 3 for c in cids], np.int32)
        a, b = self.each("stage_read_block", rows, rels, counts)
        assert np.array_equal(a, b)
        self.blocks.append((rows, self.new._epoch_gen))
        for c, sl in zip(cids, a.tolist()):
            self.open.setdefault(c, {})[sl] = self.rnd

    def echo_block(self, n):
        cids = [int(c) for c in self.rng.choice(self.leaders(), size=n)]
        rows = np.array([self._row(c) for c in cids])
        slots = self.rng.integers(0, 4, size=n)
        gi = self.new.groups
        peers = np.array([
            gi[c].slots[int(self.rng.choice(self.nodes[c]))] for c in cids
        ])
        self.each("read_ack_block", rows, slots, peers)
        self.blocks.append((rows.astype(np.int32), self.new._epoch_gen))

    # -- transitions -----------------------------------------------------
    def leader_change(self):
        """A leader with contexts pending steps down and is elected
        again: its staged events and pending slots die."""
        pending = [c for c, d in self.open.items() if d and c % 5]
        cid = int(self.rng.choice(pending or self.leaders()))
        self.term[cid] += 2
        self.last[cid] = self.new.committed_index(cid) + 1
        self.each("set_follower", cid, self.term[cid] - 1)
        self.each("set_leader", cid, term=self.term[cid],
                  term_start=self.last[cid], last_index=self.last[cid])
        self.open.pop(cid, None)
        self.did.add("leader_change")

    def campaign(self):
        """A follower campaigns and hears its votes: ``won`` / ``lost``
        beside the ticks' flags."""
        cid = int(self.rng.choice([c for c in self.term if not c % 5]))
        self.term[cid] += 1
        self.each("set_candidate", cid, self.term[cid])
        for nid in self.nodes[cid]:
            self.each("vote", cid, nid, bool(self.rng.random() < 0.6))
        self.did.add("campaign")

    def upload_without_purge(self):
        """A row is uploaded with its pending slots kept (a fresh
        randomized timeout): the decode rechecks them."""
        pending = [c for c, d in self.open.items() if d and c % 5]
        if pending:
            cid = int(self.rng.choice(pending))
            self.each("set_randomized_timeout", cid,
                      int(self.rng.integers(11, 20)))
            self.did.add("upload")

    # -- one round ---------------------------------------------------------
    def round(self):
        rng = self.rng
        single = self.arity in ("single", "mixed")
        block = self.arity in ("block", "mixed")
        self.acks(3)
        if single:
            for _ in range(int(rng.integers(1, 5))):
                self.stage(int(rng.choice(self.leaders())))
            self.stage(SOLO)
            if self.rnd == 0:
                for _ in range(5):
                    self.stage(3)  # the fifth finds no slot
        if block:
            self.stage_block(4)
            self.echo_block(5)
        if single:
            for cid, d in list(self.open.items()):
                for sl in list(d):
                    if rng.random() < 0.7:
                        self.echo(cid, sl)
            self.echo(int(rng.choice(self.leaders())))  # maybe a free slot
            self.cancel()
        if rng.random() < 0.6 or self.rnd == 1:
            self.leader_change()       # after the round's first events
        if rng.random() < 0.5 or self.rnd in (2, 3):
            self.upload_without_purge()
        if rng.random() < 0.3 or self.rnd == 4:
            self.campaign()
        if block:
            self.echo_block(3)         # after the bump: whole
            self.stage_block(2)
        if single:
            self.stage(int(rng.choice(self.leaders())))
            for cid, d in list(self.open.items()):
                for sl in list(d):
                    if rng.random() < 0.5:
                        self.echo(cid, sl)
        self.rnd += 1

    def expected_counts(self):
        """What the span must say of the rounds driven since
        ``new_round``: tuple events still of their row's epoch, blocks
        staged before a bump, the pairs a scalar decode has to visit."""
        eng = self.new
        live = [(k, r, sl) for k, r, sl, ep in self.tuples
                if ep == eng._row_epoch[r]]
        stale = sum(1 for _rows, gen in self.blocks if gen != eng._epoch_gen)
        bumped = eng._epoch_bumped
        whole = any(
            not np.isin(rows, bumped[gen - eng._epoch_gen:]).all()
            if gen != eng._epoch_gen else True
            for rows, gen in self.blocks
        )
        pairs = {(r, sl) for _k, r, sl in live}
        pending = eng.mirror.arrays["read_count"]
        for r in eng._dirty:
            pairs |= {(r, sl) for sl in range(4) if pending[r, sl] > 0}
        pairs |= eng._read_recheck
        return {
            "reads_scalar": sum(k == "stage" for k, _r, _s in live),
            "echoes_scalar": sum(k == "echo" for k, _r, _s in live),
            "read_blocks_stale": stale,
            "decode_pairs": 0 if whole else len(pairs),
        }


def _same_results(ra, rb, where):
    for f in READ_ARRAYS + ("_commit_cids", "_commit_abs"):
        a, b = getattr(ra, f), getattr(rb, f)
        assert (a is None) == (b is None), (where, f)
        if a is not None:
            assert a.dtype == b.dtype and np.array_equal(a, b), (where, f)
    assert ra.reads == rb.reads and ra.commit == rb.commit, where
    for f in FLAGS:
        assert getattr(ra, f) == getattr(rb, f), (where, f)


@pytest.mark.parametrize("seed", [35, 36])
@pytest.mark.parametrize("mode", ["step", "rounds"])
@pytest.mark.parametrize("arity", ["single", "block", "mixed"])
def test_read_staging_is_byte_identical_to_the_old(arity, mode, seed):
    pair = _Pair(arity, seed)
    confirmed = scalar = stale = visited = 0
    flagged = set()
    for step in range(10):
        where = f"{arity} {mode} seed={seed} step={step}"
        pair.new_round()
        pair.round()
        want = pair.expected_counts()
        if mode == "rounds":
            # the fused path seals each round as it closes (as arrays),
            # and its harvest scans the plane
            pair.each("begin_round")
            pair.new_round()
            pair.round()
            for k, n in pair.expected_counts().items():
                want[k] += n
            want["decode_pairs"] = 0
            ra, rb = pair.each("step_rounds", do_tick=step % 2 == 0,
                               pad_rounds_to=4)
        else:
            ra, rb = pair.each("step", do_tick=step % 2 == 0)
        _same_launches(pair, where)
        _same_results(ra, rb, where)
        assert np.array_equal(
            pair.new.committed_view(), pair.old.committed_view()), where
        for name in ("_read_busy", "_read_freed_round", "_read_next_slot",
                     "_read_echo_host"):
            assert np.array_equal(
                getattr(pair.new, name), getattr(pair.old, name)), where
        for c, sl, _idx, _n in ra.reads:
            pair.open.get(c, {}).pop(sl, None)
        confirmed += len(ra.reads)
        flagged |= {f for f in FLAGS if getattr(ra, f)}
        span = [s for s in pair.rec.spans()
                if s["kind"] in ("dispatch", "fused")][-1]
        got = {k: span[k] for k in want}
        assert got == want, where
        assert span["reads"] >= span["reads_scalar"], where
        assert span["echoes"] >= span["echoes_scalar"], where
        scalar += got["reads_scalar"] + got["echoes_scalar"]
        stale += got["read_blocks_stale"]
        visited += got["decode_pairs"]
    assert not pair.mismatches
    # nothing staged is left behind, and no bump is remembered
    for eng in (pair.new, pair.old):
        assert not eng._reads_pending() and eng._epoch_bumped == []
    # the script exercised what it claims to
    assert confirmed and {"leader_change", "campaign"} <= pair.did
    assert "heartbeat" in flagged and flagged & {"won", "lost"}
    assert bool(scalar) == (arity != "block")
    assert bool(stale) == (arity != "single")
    assert bool(visited) == (arity == "single" and mode == "step")
    if arity != "block":
        assert {"overflow", "cancel", "upload"} <= pair.did


def test_a_quorum_of_one_confirms_at_its_stage():
    """The candidate pairs of a dispatch include the slots it staged: a
    group with one voter confirms with no echo at all."""
    eng = BatchedQuorumEngine(G, P)
    eng.add_group(SOLO, node_ids=[1], self_id=1)
    eng.set_leader(SOLO, term=1, term_start=1, last_index=1)
    eng.step(do_tick=False)
    slot = eng.stage_read(SOLO, count=2, index=1)
    res = eng.step(do_tick=False)
    assert res.reads == [(SOLO, slot, 1, 2)]
    assert res.read_counts.dtype == np.int64


def test_an_upload_rechecks_the_slots_it_left_pending():
    """A slot pending on the device confirms with no event of its own
    once an upload changes what ``read_confirm`` reads of its row: the
    decode visits the pending slots of every uploaded row.  Here the
    quorum falls from three to one under a pending context."""
    eng = BatchedQuorumEngine(G, P)
    eng.add_group(1, node_ids=[1, 2, 3, 4, 5], self_id=1)
    eng.set_leader(1, term=1, term_start=1, last_index=1)
    slot = eng.stage_read(1, count=1, index=1)
    assert eng.step(do_tick=False).reads == []  # pending: no echo yet
    row = eng.groups[1].row
    eng._sync_row(row)
    eng.mirror.arrays["quorum"][row] = 1
    eng._dirty.add(row)
    other = eng.add_group(2, node_ids=[1, 2, 3], self_id=1)
    eng.set_leader(2, term=1, term_start=1, last_index=1)
    eng.stage_read(2, count=1, index=1)  # the dispatch runs the read plane
    res = eng.step(do_tick=False)
    assert res.reads == [(1, slot, 1, 1)]
    assert other.row != row


def test_an_assigned_device_state_is_scanned_whole():
    """After ``eng.dev = ...`` the engine cannot know which slots the
    state holds: the next read-plane decode scans the plane."""
    a = BatchedQuorumEngine(G, P)
    b = BatchedQuorumEngine(G, P)
    for eng in (a, b):
        eng.add_group(1, node_ids=[1], self_id=1)
        eng.add_group(2, node_ids=[1, 2, 3], self_id=1)
        for cid in (1, 2):
            eng.set_leader(cid, term=1, term_start=1, last_index=1)
        eng.step(do_tick=False)
    # b gets a's state with a context staged on it but not yet confirmed:
    # a's quorum of three is b's quorum of one after the swap below
    slot = a.stage_read(2, count=3, index=1)
    a.step(do_tick=False)
    st = a.dev
    row = b.groups[2].row
    st = st._replace(quorum=st.quorum.at[row].set(1))
    b.dev = st
    assert b._read_recheck is None
    b.stage_read(1, count=1, index=1)
    res = b.step(do_tick=False)
    assert (2, slot, 1, 3) in res.reads and len(res.reads) == 2
    assert b._read_recheck == set()
