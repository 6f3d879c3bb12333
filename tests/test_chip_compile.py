"""The live path's kernels compile for a v5e — checked without one.

The TPU compiler is installed in CI; it compiles for a *described*
``v5e:2x2`` chip (nothing is attached, nothing runs).  Every program the
live path dispatches is compiled at the BASELINE ladder's size
(65,536 groups x 5 peers) and at the live coordinator's default
(1,024 x 8), with the static flags the live path turns on
(``has_reads`` / ``has_kv`` / ``has_telem`` / ``has_hier``); one mesh case
partitions the state over the four described devices.  The three live
programs (``ops/packed.py``: the state as blocks, one ingress block) take
their ingress and statics from the engine's own ``_variant_args``
builder, so what compiles here is what the coordinator dispatches.

The topology is described inside a module-scoped fixture: only one
process may load the TPU library, so it must never happen at import (every
xdist worker imports every test file) and these tests must stay in ONE
file.  The persistent compilation cache is switched off around them: an
executable compiled for a described chip is written to the cache but can
never be read back without the chip.
"""
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from dragonboat_tpu.ops.engine import BatchedQuorumEngine  # noqa: E402
from dragonboat_tpu.ops.sharding import GROUP_AXIS, block_sharding  # noqa: E402
from dragonboat_tpu.ops.state import make_state, pack_state  # noqa: E402

#: (groups, peers): BASELINE.json's ladder size, and tpuquorum.py's default
SIZES = {"ladder": (65536, 5), "live": (1024, 8)}

#: warm-plan variants (kind, arg, has_reads, has_kv) — the closed set the
#: live coordinator dispatches, at its largest K bucket
LIVE_VARIANTS = [
    ("sparse", True, False, False),
    ("sparse_votes", True, False, False),
    ("dense", True, True, False),
    ("dense", True, True, True),
    ("fused", 16, False, False),
    ("fused", 16, True, False),
    ("fused", 16, True, True),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax._src import compilation_cache as _jcc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    _jcc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    _jcc.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: None if s is None else jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding
        ),
        tree,
        is_leaf=lambda x: x is None,
    )


def _blocks(groups, peers, sharding):
    """The packed carry the engine's programs take, as shapes."""
    return _on(sharding, jax.eval_shape(
        lambda: pack_state(make_state(groups, peers))
    ))


def _engine(groups, peers):
    """A live-shaped engine (the coordinator's event_cap rule) with the
    telemetry and hier latches up, as a fully featured NodeHost runs it."""
    eng = BatchedQuorumEngine(
        groups, peers, event_cap=max(4 * groups, 4096), device_ticks=True
    )
    eng.enable_telem()
    eng._hier_used = True
    return eng


def _compile(fn, st, args, statics):
    compiled = fn.lower(st, *args, **statics).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    return compiled


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize(
    "variant", LIVE_VARIANTS,
    ids=[BatchedQuorumEngine.variant_label(*v) for v in LIVE_VARIANTS],
)
def test_live_program_compiles_for_v5e(topo, no_compile_cache, size, variant):
    groups, peers = SIZES[size]
    one_chip = SingleDeviceSharding(topo.devices[0])
    fn, ing, statics = _engine(groups, peers)._variant_args(
        *variant, abstract=True
    )
    assert statics["has_telem"] and statics["has_hier"]
    _compile(
        fn, _blocks(groups, peers, one_chip), (_on(one_chip, ing),), statics
    )


#: what ``idle1024x3``'s hosts dispatch: the quiesce latch alone
QUIESCE_VARIANTS = [
    ("sparse", True, False, False),
    ("dense", True, True, False),
    ("fused", 16, False, False),
    ("fused", 16, True, False),
]


@pytest.mark.parametrize(
    "variant", QUIESCE_VARIANTS,
    ids=[BatchedQuorumEngine.variant_label(*v) for v in QUIESCE_VARIANTS],
)
def test_quiesce_program_compiles_for_v5e(topo, no_compile_cache, variant):
    """The ``has_quiesce`` twins (the tick kernel's idle clocks, the marks
    taken off the ack plane's last peer slot) at the live size."""
    groups, peers = SIZES["live"]
    one_chip = SingleDeviceSharding(topo.devices[0])
    eng = BatchedQuorumEngine(
        groups, peers, event_cap=max(4 * groups, 4096), device_ticks=True
    )
    eng.enable_quiesce()
    fn, ing, statics = eng._variant_args(*variant, abstract=True)
    assert statics["has_quiesce"] and not statics["has_telem"]
    _compile(
        fn, _blocks(groups, peers, one_chip), (_on(one_chip, ing),), statics
    )


def test_group_sharded_program_compiles_for_v5e_2x2(topo, no_compile_cache):
    """The GSPMD form: state split over the four described devices on the
    group axis, the fused K-round block split the same way."""
    groups, peers = 4 * SIZES["ladder"][0], SIZES["ladder"][1]
    mesh = Mesh(np.array(topo.devices), (GROUP_AXIS,))
    assert mesh.devices.size == 4
    # the blocks are (rows, G): the leaves' P(groups) moves one axis to
    # the right
    st = _blocks(
        groups, peers, block_sharding(NamedSharding(mesh, P(GROUP_AXIS)))
    )
    fn, ing, statics = _engine(groups, peers)._variant_args(
        "fused", 16, True, False, abstract=True
    )
    # the ingress block is one flat host buffer: replicated
    compiled = _compile(
        fn, st, (_on(NamedSharding(mesh, P()), ing),), statics
    )
    # what a device holds of the arguments, less the replicated ingress
    per_device = compiled.memory_analysis().argument_size_in_bytes - (
        int(np.prod(ing.shape)) * np.dtype(ing.dtype).itemsize
    )
    whole = sum(
        int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
        for s in jax.tree_util.tree_leaves(st)
    )
    assert per_device <= whole // 3, "state was not partitioned across the mesh"
