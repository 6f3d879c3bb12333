# dragonboat_tpu developer entry points (reference Makefile roles:
# test / monkey-test / benchmark — docs/test.md)

PY ?= python

.PHONY: test test-all test-kernels test-obs test-trace test-warmup \
	test-hostplane test-hostproc test-lease test-devsm test-health \
	test-repltrace test-devprof test-mesh test-recovery test-hiercommit \
	native soak soak-smoke soak-churn soak-churn-smoke dryrun

test: native
	$(PY) -m pytest tests/ -x -q -m "not slow"

# fast local gate for kernel changes: the device-engine differential
# suites (fused ≡ single-round ≡ scalar oracle, incl. the read plane)
# standalone on the cpu backend — run this before the full tier-1 sweep
# whenever ops/kernels.py, ops/state.py, or ops/engine.py change
test-kernels:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_ops_quorum.py \
	    tests/test_multiround.py tests/test_read_confirm.py -q

# fast cpu gate for the observability plane (mirrors test-kernels): the
# flight recorder, Prometheus exposition round-trip, obs on/off engine
# parity and the stall-watchdog auto-dump — run before the full tier-1
# sweep whenever obs/, events.py, or the engine/coordinator hooks change
test-obs:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_obs.py tests/test_events.py -q

# fast cpu gate for cross-plane request tracing (ISSUE 9): trace-off
# structural identity (compartments on/off), stage-chain completeness on
# the scalar/tpu/fused paths incl. a membership recycle mid-trace, the
# stage-level stall watchdog (ErrorFS WAL stall), and the Perfetto
# export — run before the full tier-1 sweep whenever obs/trace.py,
# requests.py, or the node/engine/coordinator trace hooks change
test-trace:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_trace.py -q

# fast cpu gate for replication-path tracing + commit quorum attribution
# (ISSUE 14): trace-off structural identity on the chan AND tcp wires
# (codec byte-identity included), leader→follower→leader stage
# completeness, quorum-closing-peer vs the scalar kth-ack oracle under
# an injected slow peer, term-pinned records across leadership
# transfer, the multi-host Perfetto merge, and the transport/latency
# introspection satellites — run before the full tier-1 sweep whenever
# obs/replattr.py, wire/codec.py's trace carriage, transport metrics or
# the raft ack/commit hooks change
test-repltrace:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_repltrace.py -q

# fast cpu gate for the AOT warm-compile + persistent compilation cache
# (ISSUE 7): warmup against a temp cache dir asserts (a) a second enable
# is cache-hot (zero recompiles after jax.clear_caches) and (b)
# proposals issued during warmup never block on compilation — plus the
# live K-batched ≡ single-round ≡ scalar differential
test-warmup:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_warmup.py \
	    tests/test_live_fused.py -q

# fast cpu gate for the compartmentalized host plane (ISSUE 8): the
# batched-ingress ≡ direct-propose differential, SystemBusy/PayloadTooBig
# semantics, group-commit merge/error-propagation, ErrorFS flusher
# crash-durability (nothing acked before its fsync), journal replay, and
# the compartments-off structural bit-identity — run before the full
# tier-1 sweep whenever hostplane.py, engine.py, requests.py, queue.py
# or logdb/{kv,sharded,journal}.py change
test-hostplane:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_hostplane.py -q

# fast cpu gate for the multi-process host plane (ISSUE 12): shm-ring
# wraparound/backpressure units, the encode-worker ≡ inline oracle, the
# ProcStateMachine differential (incl. kill -9 exactly-once fallback and
# self-rebase), WAL-worker durability (injected fsync failure fails the
# whole flush cycle; dead worker degrades in-process), the rdbcache
# failed-commit invalidation, and the workers-off structural identity —
# run before the full tier-1 sweep whenever hostproc/, hostplane.py,
# logdb/{journal,rdb,sharded}.py or the nodehost wiring change
test-hostproc:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_hostproc.py -q

# fast cpu gate for the device state machine (ISSUE 11): the device KV
# apply ≡ scalar-oracle differential (kernel + engine level), the
# recycle/transition/snapshot semantics, the devsm-off structural
# identity, and the live single-node + 3-node failover paths — run
# before the full tier-1 sweep whenever ops/kernels.py's kv plane,
# ops/state.py's kv arrays, devsm/, or the coordinator/raft devsm hooks
# change
test-devsm:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_devsm.py -q

# fast cpu gate for the cluster health plane (ISSUE 13): health-off
# structural identity, the detector fault-injection suite (ErrorFS WAL
# stall -> commit_stall, netsplit -> quorum_at_risk, kill -9 ->
# worker_flap with measured recovery), the detector unit semantics on
# synthetic samples, and the /metrics + /healthz endpoint round-trip —
# run before the full tier-1 sweep whenever obs/health.py,
# obs/instruments.py, the nodehost health wiring or the plane
# health_snapshot accessors change
test-health:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_health.py -q

# fast cpu gate for the closed-loop recovery plane (ISSUE 17): the
# actuation matrix on a scripted NodeHost stub (quorum_at_risk ->
# evict+promote, leader_flap -> transfer with the hold-when-all-flapped
# rule, devsm_rebind -> force release, commit_stall -> fast-lane
# redrive, worker_flap observe-only), every guardrail (rate limit,
# cooldown, strike suppression, not_leader retries, dry run), the
# recovery-off structural identity and the live netsplit MTTR A/B —
# test_health runs FIRST (the recovery suite mutates the default
# detector registry; alphabetical tier-1 order already guarantees this)
# — run before the full tier-1 sweep whenever obs/recovery.py,
# obs/health.py's subscription API or the nodehost recovery wiring
# change
test-recovery:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_health.py \
	    tests/test_recovery.py -q

# fast cpu gate for the device capacity & profiling plane (ISSUE 15):
# profile-off structural identity, the HBM ledger ≡ live-array bytes
# differential, the capacity model's no-drift assertions against the
# shared upload accounting, warm-set program-registry coverage,
# padding-waste accounting and the /debug/devprof + capture-window
# lifecycle — run before the full tier-1 sweep whenever obs/devprof.py,
# the engine's dispatch accounting or ops/state.py's layout change
test-devprof:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_devprof.py -q

# fast cpu gate for the mesh-sharded dispatch plane (ISSUE 16): the
# mesh ≡ single-device ≡ scalar-oracle commit/read differentials, live
# migration with watermark preservation + the quiescence refusal,
# cost-driven rebalancing, verifiably-overlapping per-shard dispatch
# spans (the no-global-mutex proof), mesh warmup readiness, and the
# full 3-NodeHost sharded stack — run before the full tier-1 sweep
# whenever ops/mesh.py, ops/engine.py's dispatch path, the coordinator
# mesh branch or the placement/rebalance logic change
test-mesh:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_mesh_dispatch.py \
	    tests/test_sharding.py -q

# fast cpu gate for the leader-lease read plane (ISSUE 10): the
# lease ≡ ReadIndex ≡ scalar-oracle differential, the invalidation
# matrix (expiry/transfer-cede/membership/term), clock-jump fault
# injection caught by the linearizability checker, the cross-domain
# live-stack reads and the lease metric families — run before the full
# tier-1 sweep whenever lease.py, raft/raft.py's read path,
# transport/latency.py or the coordinator lease table change
test-lease:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_lease.py -q

# fast cpu gate for the hierarchical commit plane (ISSUE 18): sub-quorum
# ≡ classic differentials, the fused class-mask rule vs the scalar
# oracle, leader-change intersection safety and far-read batching — run
# before the full tier-1 sweep whenever raft/hier.py, the raft commit or
# vote paths, or the engine's hier fold change
test-hiercommit:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_hiercommit.py -q

# fast cpu gate for the device telemetry fold (ISSUE 20): the fold ≡
# host-oracle differential (sparse/fused/mesh paths, mid-block recycle,
# migration), stalled-watermark and top-K tie semantics, the telem-off
# structural identity, the aggregate sampler's drill-down walk +
# hysteresis units, the busy-row degradation counters, and the chunked
# /metrics + /debug/telem endpoints — run before the full tier-1 sweep
# whenever ops/kernels.py's telem fold, ops/state.py's telem plane,
# obs/health.py's aggregate mode or the engine/mesh harvest change
test-telem:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_telem.py -q

# parallel run, as the driver's tier-1 does it: tests marked
# xdist_group("heavy-multiprocess") hold one file lock each
# (tests/conftest.py), so live multi-NodeHost clusters never run side
# by side while the light majority fans out
test-par: native
	$(PY) -m pytest tests/ -q -m "not slow" -n 6 --dist loadfile

test-all: native
	$(PY) -m pytest tests/ -x -q

native:
	$(MAKE) -C dragonboat_tpu/native

# race-detection gate for the C++ engine (the reference's RACE=1 make
# test role, docs Makefile:122-127): native suites under ThreadSanitizer.
# Scoped to the timing-robust modules — TSAN's 5-15x slowdown makes the
# enrollment-pacing chaos tests assert on scheduling, not races.
TSAN_RT := $(shell $(CXX) -print-file-name=libtsan.so)
TSAN_ENV = DBTPU_NATIVE_LIB_DIR=$(CURDIR)/dragonboat_tpu/native/tsan \
	LD_PRELOAD=$(TSAN_RT) \
	TSAN_OPTIONS="halt_on_error=0 report_thread_leaks=0 exitcode=66"
test-tsan:
	test -f "$(TSAN_RT)"  # libtsan runtime must exist
	$(MAKE) -C dragonboat_tpu/native tsan
	# the targeted suites skip themselves when the libs fail to load —
	# assert loadability FIRST so a broken TSAN env can't pass vacuously
	$(TSAN_ENV) $(PY) -c "from dragonboat_tpu.native import natraft, natsm, available; \
	    assert available() and natraft.available() and natsm.available(), \
	    'TSAN native libs failed to load'"
	$(TSAN_ENV) $(PY) -m pytest tests/test_natsm.py tests/test_partition_tcp.py \
	    tests/test_nativekv.py -q

# Drummer-analog chaos soak (docs/test.md:6-36): kill -9/restart churn,
# continuous cross-replica hash checks, linearizability on sampled keys
soak: native
	$(PY) soak.py --minutes 10 --groups 16

soak-smoke: native
	$(PY) soak.py --minutes 1 --groups 8

# native-plane soak: C-ABI KV + native exactly-once session store under
# the same churn — session-managed history clients retry unknown
# outcomes against the dedup store (at-most-once apply), and session
# hashes join the cross-replica convergence check
soak-native: native
	SOAK_NATIVE_SM=1 SOAK_SESSIONS=1 $(PY) soak.py --minutes 10 --groups 16

soak-native-smoke: native
	SOAK_NATIVE_SM=1 SOAK_SESSIONS=1 $(PY) soak.py --minutes 1 --groups 8

# BlackWater churn soak (ISSUE 17): 100 witness-heavy groups over 4
# hosts under leader-flap storms, netsplit holds, SIGSTOP stalls,
# kill -9 restarts and membership recycles — run twice with the same
# seed (once plain, once --recover) to reproduce the MTTR A/B the
# bench's churn_soak axis scores
soak-churn: native
	$(PY) soak.py --churn --minutes 1 --groups 100 --seed 7
	$(PY) soak.py --churn --minutes 1 --groups 100 --seed 7 --recover

soak-churn-smoke: native
	$(PY) soak.py --churn --minutes 0.1 --groups 20 --seed 7
	$(PY) soak.py --churn --minutes 0.1 --groups 20 --seed 7 --recover

dryrun:
	$(PY) __graft_entry__.py

# the main path on the real TPU, one process (fails without a chip;
# `$(PY) chip_smoke.py --rehearse-cpu` is the tiny-size CPU rehearsal)
chip-smoke:
	$(PY) chip_smoke.py
