"""Batched quorum engine: the TPU-native heart of the framework.

The reference iterates thousands of Raft groups one at a time
(``execengine.go:923`` ``processSteps``; ``internal/raft/raft.go:861-909``
``tryCommit``; ``raft.go:1062-1080`` vote tally).  Here the per-group,
per-tick dense bookkeeping lives in ``(nGroups, nPeers)`` device arrays
stepped by ONE fused jit dispatch per tick (SURVEY.md §7), while rare
control-flow-heavy transitions (membership change, snapshot install, log
rejection backtracking) remain scalar on host and mask-update the tensors.

Modules:

* :mod:`.state`   — the ``QuorumState`` pytree layout + host<->device codec
* :mod:`.kernels` — pure jit kernels (commit quorum, vote tally, tick, ...)
* :mod:`.engine`  — ``BatchedQuorumEngine`` host driver (delta ingest,
  one dispatch per tick, egress of flags/commit advances)
* :mod:`.sharding` — device-mesh sharding of the group axis for multi-chip
"""

from .state import QuorumState, make_state, INDEX_MIN  # noqa: F401
from .kernels import (  # noqa: F401
    commit_quorum,
    vote_tally,
    check_quorum,
    tick_step,
    quorum_step,
    quorum_step_dense,
)
from .engine import BatchedQuorumEngine  # noqa: F401
