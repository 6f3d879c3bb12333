"""The engine's device programs: ``unpack -> the kernel as it is -> pack``.

A step on the round thread pays a hand-off of the interpreter for every
``jax.Array`` it makes and retires, whatever its size (PERF.md §5).  So
what crosses the host/device boundary of one dispatch is held to:

* the state, carried as two blocks (``state.StateBlocks``), donated;
* ONE ingress block: everything the dispatch stages, in one flat ``int32``
  host buffer (:class:`Ingress`) whose sections the program slices;
* ONE egress block: ``(rows, G)`` ``int32`` — the commit watermark, the
  six flag vectors as one bit field, and the read / kv captures of the
  planes in use as further rows.

The layout is one rule (:func:`ingress_sections`, :func:`split_egress`)
computed from what the engine already knows — G, P, the event cap, K, the
slot counts and the planes a dispatch carries — and read by both sides:
the host stages through the views of an :class:`Ingress`, the program
slices with the same section list.  The kernels' arithmetic does not
change, and the programs keep the kernels' names in a device trace
(``jit_quorum_step_impl`` ...): what ran before under that name is what
runs now, between a few slices and one concatenate.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from . import kernels as _k
from .state import (
    I8, I32, VOTE_NONE, StateBlocks, block_dims, pack_state, unpack_state,
)

#: the egress bit field, low bit first (``StepResult`` field of each)
FLAG_BITS = ("won", "lost", "elect", "heartbeat", "demote", "quiesce")


class PackedOut(NamedTuple):
    blocks: StateBlocks
    egress: jax.Array                       # (rows, G) i32
    telem: _k.TelemAggregate | None = None  # has_telem only: its own arrays


# ----------------------------------------------------------------------
# the layout rule
# ----------------------------------------------------------------------

def ingress_sections(
    kind: str, g: int, p: int, dims: tuple, *, cap: int = 0, k: int = 0,
    c: int = 0, has_votes: bool = False, has_churn: bool = False,
    do_tick: bool = False, has_reads: bool = False, has_kv: bool = False,
) -> tuple:
    """``(name, shape, fill)`` of every section of one dispatch's ingress
    block, in buffer order.  ``kind``: ``"sparse"`` (event lists of
    ``cap``), ``"dense"`` ((G,P) matrices) or ``"fused"`` (K rounds of
    them); ``dims`` is ``(n_read_slots, n_kv_slots, n_kv_ents,
    n_kv_reads)``.  ``fill`` is what a section holds where nothing was
    staged (None: what lies beyond the staged count is never read)."""
    s, _v, e, r = dims
    if kind == "sparse":
        # event counts first: validity is ``iota < count`` in-program
        lists = ("ack_g", "ack_p", "ack_val") + (
            ("vote_g", "vote_p", "vote_grant") if has_votes else ()
        )
        return (("n", (2,), 0),) + tuple((n, (cap,), None) for n in lists)
    lead = (k,) if kind == "fused" else ()
    # -1 = untouched: one plane instead of (max, touched)
    out = [("ack", lead + (g, p), -1)]
    if has_votes:
        out.append(("votes", lead + (g, p), VOTE_NONE))
    if has_churn:  # fused only; row g = padding (drops)
        out.append(("churn_row", (k, c), g))
        out += [(n, (k, c), 0)
                for n in ("churn_term", "churn_start", "churn_last")]
    if has_reads:
        assert p <= 31, "read echoes ride as one int32 bit field a slot"
        out += [("read_idx", lead + (g, s), -1),
                ("read_cnt", lead + (g, s), 0),
                ("read_echo", lead + (g, s), 0)]  # bit pe = peer pe echoed
    if has_kv:
        out += [("kv_idx", lead + (g, e), -1), ("kv_key", lead + (g, e), 0),
                ("kv_val", lead + (g, e), 0), ("kv_rkey", lead + (g, r), -1)]
    if kind == "fused" and do_tick:
        out.append(("tick", (k,), 0))
    return tuple(out)


def ingress_size(sections: tuple) -> int:
    """Elements (int32) of the ingress block ``sections`` lay out."""
    return sum(int(np.prod(shape)) for _n, shape, _f in sections)


def split_egress(eg, dims: tuple, has_reads: bool, has_kv: bool) -> tuple:
    """A fetched egress block, ``(rows, G)``, as views: ``(committed, flag
    bits, read done count, read done index, kv read value, kv read index,
    kv applied)`` — watermark and flags first, then per plane the
    dispatch carried the read confirmations (S rows each) and the kv
    captures (R rows each, and one of applied counts); None for a plane
    it ran without.  The host side of :func:`_pack_egress`."""
    s_, _v, _e, r = dims
    at = 2
    rdc = rdi = kvv = kvi = kva = None
    if has_reads:
        rdc, rdi = eg[at:at + s_].T, eg[at + s_:at + 2 * s_].T
        at += 2 * s_
    if has_kv:
        kvv, kvi, kva = (
            eg[at:at + r].T, eg[at + r:at + 2 * r].T, eg[at + 2 * r]
        )
    return eg[0], eg[1], rdc, rdi, kvv, kvi, kva


class Ingress:
    """One preallocated host buffer for everything a dispatch of one
    shape stages, and the views its sections are written through.

    ``cells`` is what the last staging says it wrote by scalar stores:
    section name -> the indexes it stored at.  :meth:`reset` puts back
    just those; a section the staging does not name (one it wrote through
    index arrays, or does not know) is refilled whole."""

    __slots__ = ("buf", "views", "cells", "_fills")

    def __init__(self, sections: tuple):
        self.buf = np.zeros((ingress_size(sections),), np.int32)
        self.views = {}
        self.cells = {}
        self._fills = []
        at = 0
        for name, shape, fill in sections:
            view = self.buf[at:at + int(np.prod(shape))].reshape(shape)
            at += view.size
            self.views[name] = view
            if fill is not None:
                view.fill(fill)
                self._fills.append((name, view, fill))

    def reset(self) -> None:
        cells, self.cells = self.cells, {}
        for name, view, fill in self._fills:
            stored = cells.get(name)
            if stored is None:
                view.fill(fill)
            else:
                for at in stored:
                    view[at] = fill


def _split(ingress: jax.Array, sections: tuple) -> dict:
    out, at = {}, 0
    for name, shape, _fill in sections:
        n = int(np.prod(shape))
        out[name] = ingress[at:at + n].reshape(shape)
        at += n
    return out


def _pack_egress(out: _k.StepOutputs) -> jax.Array:
    flags = (out.won, out.lost) + tuple(out.flags)
    bits = sum(f.astype(I32) << i for i, f in enumerate(flags))
    rows = [out.committed[None], bits[None]]
    if out.read_done_count is not None:
        rows += [out.read_done_count.T, out.read_done_index.T]
    if out.kv_read_index is not None:
        rows += [out.kv_read_val.T, out.kv_read_index.T,
                 out.kv_applied[None]]
    return jnp.concatenate(rows, axis=0)


def _finish(out: _k.StepOutputs) -> PackedOut:
    return PackedOut(pack_state(out.state), _pack_egress(out), out.telem)


def sparse_events(sec: dict, cap: int, has_votes: bool) -> tuple:
    """The sparse kernel's eight event arguments out of the sections: the
    ack lists and the vote lists, each with its ``iota < count`` mask."""
    iota = jnp.arange(cap, dtype=I32)
    acks = (sec["ack_g"], sec["ack_p"], sec["ack_val"], iota < sec["n"][0])
    if has_votes:
        return acks + (
            sec["vote_g"], sec["vote_p"], sec["vote_grant"].astype(I8),
            iota < sec["n"][1],
        )
    z = jnp.zeros((1,), I32)  # compiled out; the kernel takes dummies
    return acks + (z, z, jnp.zeros((1,), I8), jnp.zeros((1,), jnp.bool_))


def _plane_args(sec: dict, p: int, has_reads: bool, has_kv: bool) -> tuple:
    """The kernels' seven read / kv arguments out of the sections."""
    if has_reads:
        echo = (
            (sec["read_echo"][..., None] >> jnp.arange(p, dtype=I32)) & 1
        ) != 0
        reads = (sec["read_idx"], sec["read_cnt"], echo)
    else:
        reads = (None, None, None)
    kv = (
        (sec["kv_idx"], sec["kv_key"], sec["kv_val"], sec["kv_rkey"])
        if has_kv else (None, None, None, None)
    )
    return reads + kv


# ----------------------------------------------------------------------
# the programs
# ----------------------------------------------------------------------

def _named(name: str):
    """The name a program carries in a device trace and in
    ``compilation_log()`` (``jit_<name>``): the kernel's."""
    def deco(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn
    return deco


@_named("quorum_step_impl")
def _step(
    blocks: StateBlocks, ingress: jax.Array, *, dims: tuple, cap: int,
    do_tick: bool = True, track_contact: bool = True, has_votes: bool = True,
    has_hier: bool = False, has_telem: bool = False,
    telem_k: int = _k.TELEM_TOPK, has_reads: bool = False,
    has_kv: bool = False, has_quiesce: bool = False,
) -> PackedOut:
    g, p = block_dims(blocks, dims[:3])
    sec = _split(ingress, ingress_sections(
        "sparse", g, p, dims, cap=cap, has_votes=has_votes))
    return _finish(_k.quorum_step_impl(
        unpack_state(blocks, dims[:3]),
        *sparse_events(sec, cap, has_votes),
        do_tick=do_tick, track_contact=track_contact, has_votes=has_votes,
        has_hier=has_hier, has_telem=has_telem, telem_k=telem_k,
        has_reads=has_reads, has_kv=has_kv, has_quiesce=has_quiesce,
    ))


@_named("quorum_step_dense_impl")
def _step_dense(
    blocks: StateBlocks, ingress: jax.Array, *, dims: tuple,
    do_tick: bool = True, track_contact: bool = True, has_votes: bool = True,
    has_reads: bool = False, has_kv: bool = False, has_hier: bool = False,
    has_telem: bool = False, telem_k: int = _k.TELEM_TOPK,
    has_quiesce: bool = False,
) -> PackedOut:
    g, p = block_dims(blocks, dims[:3])
    sec = _split(ingress, ingress_sections(
        "dense", g, p, dims, has_votes=has_votes, has_reads=has_reads,
        has_kv=has_kv))
    ack = sec["ack"]
    return _finish(_k.quorum_step_dense_impl(
        unpack_state(blocks, dims[:3]),
        jnp.maximum(ack, 0),  # -1 sentinel -> 0 (a max no-op)
        ack >= 0,
        sec["votes"].astype(I8) if has_votes else jnp.zeros((1, 1), I8),
        *_plane_args(sec, p, has_reads, has_kv),
        do_tick=do_tick, track_contact=track_contact, has_votes=has_votes,
        has_reads=has_reads, has_kv=has_kv, has_hier=has_hier,
        has_telem=has_telem, telem_k=telem_k, has_quiesce=has_quiesce,
    ))


@_named("quorum_multiround_impl")
def _multiround(
    blocks: StateBlocks, ingress: jax.Array, *, dims: tuple, k: int,
    c: int = 0, do_tick: bool = False, track_contact: bool = True,
    has_votes: bool = False, has_churn: bool = False,
    has_reads: bool = False, purge_reads: bool = True, has_kv: bool = False,
    purge_kv: bool = True, has_hier: bool = False, has_telem: bool = False,
    purge_telem: bool = True, telem_k: int = _k.TELEM_TOPK,
    has_quiesce: bool = False,
) -> PackedOut:
    g, p = block_dims(blocks, dims[:3])
    sec = _split(ingress, ingress_sections(
        "fused", g, p, dims, k=k, c=c, has_votes=has_votes,
        has_churn=has_churn, do_tick=do_tick, has_reads=has_reads,
        has_kv=has_kv))
    z = jnp.zeros((1, 1), I32)  # what a compiled-out plane is handed
    churn = tuple(
        sec[n] if has_churn else z
        for n in ("churn_row", "churn_term", "churn_start", "churn_last")
    )
    return _finish(_k.quorum_multiround_impl(
        unpack_state(blocks, dims[:3]),
        sec["ack"],
        sec["votes"].astype(I8) if has_votes else jnp.zeros((1, 1, 1), I8),
        *churn,
        sec["tick"] != 0 if do_tick else jnp.zeros((k,), jnp.bool_),
        *_plane_args(sec, p, has_reads, has_kv),
        do_tick=do_tick, track_contact=track_contact, has_votes=has_votes,
        has_churn=has_churn, has_reads=has_reads, purge_reads=purge_reads,
        has_kv=has_kv, purge_kv=purge_kv, has_hier=has_hier,
        has_telem=has_telem, purge_telem=purge_telem, telem_k=telem_k,
        has_quiesce=has_quiesce,
    ))


quorum_step = jax.jit(
    _step,
    static_argnames=(
        "dims", "cap", "do_tick", "track_contact", "has_votes", "has_hier",
        "has_telem", "telem_k", "has_reads", "has_kv", "has_quiesce",
    ),
    donate_argnums=(0,),
)

quorum_step_dense = jax.jit(
    _step_dense,
    static_argnames=(
        "dims", "do_tick", "track_contact", "has_votes", "has_reads",
        "has_kv", "has_hier", "has_telem", "telem_k", "has_quiesce",
    ),
    donate_argnums=(0,),
)

quorum_multiround = jax.jit(
    _multiround,
    static_argnames=(
        "dims", "k", "c", "do_tick", "track_contact", "has_votes",
        "has_churn", "has_reads", "purge_reads", "has_kv", "purge_kv",
        "has_hier", "has_telem", "purge_telem", "telem_k", "has_quiesce",
    ),
    donate_argnums=(0,),
)


# ----------------------------------------------------------------------
# the blocks' own small programs (rare path: row syncs, ``eng.dev``)
# ----------------------------------------------------------------------

pack = jax.jit(pack_state)
unpack = jax.jit(unpack_state, static_argnames=("dims",))


def _gather_rows_impl(blocks, idx, *, dims, keys):
    st = unpack_state(blocks, dims)
    return {k: getattr(st, k)[idx] for k in keys}


#: rows ``idx`` of the ``keys`` leaves in ONE program, one transfer
gather_rows = jax.jit(
    _named("gather_rows")(_gather_rows_impl),
    static_argnames=("dims", "keys"),
)


def _scatter_rows_impl(blocks, idx, vals, *, dims):
    st = unpack_state(blocks, dims)
    return pack_state(st._replace(**{
        k: getattr(st, k).at[idx].set(v) for k, v in vals.items()
    }))


#: ``blocks`` with rows ``idx`` of the ``vals`` leaves overwritten (the
#: upload twin of :func:`gather_rows`); the old blocks are donated
scatter_rows = jax.jit(
    _named("scatter_rows")(_scatter_rows_impl),
    static_argnames=("dims",), donate_argnums=(0,),
)
