"""Transport manager: per-remote queues, batching, breakers, snapshot jobs.

Reference: ``internal/transport/transport.go`` — lazily spawned per-remote
sender (CockroachDB async-send pattern, ``transport.go:16-18``), message
batching up to 64MB, per-address circuit breaker, deployment-id filtering on
receive, and the chunked snapshot send plane (``snapshot.go``/``job.go``).
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Callable, Dict, Optional

from ..logger import get_logger
from ..settings import Soft
from ..wire import Chunk, Message, MessageBatch, MessageType
from .registry import Registry
from .rpc import IRaftRPC, TransportError

plog = get_logger("transport")


class CircuitBreaker:
    """Minimal failure-fast breaker (plays the role of the reference's
    rubyist/circuitbreaker usage, ``transport.go:268``)."""

    def __init__(self, fail_threshold: int = 3, reset_seconds: float = 5.0):
        self.fail_threshold = fail_threshold
        self.reset_seconds = reset_seconds
        self._mu = threading.Lock()
        self._failures = 0
        self._opened_at = 0.0

    def ready(self) -> bool:
        with self._mu:
            if self._failures < self.fail_threshold:
                return True
            # half-open after the reset window
            return time.monotonic() - self._opened_at >= self.reset_seconds

    def success(self) -> None:
        with self._mu:
            self._failures = 0

    def fail(self) -> None:
        with self._mu:
            self._failures += 1
            if self._failures >= self.fail_threshold:
                self._opened_at = time.monotonic()


class SendQueue:
    """The messages waiting for one remote: many producers (step workers,
    committers, coordinator round threads), one consumer (the remote's
    sender thread).  A ``deque`` and an ``Event`` instead of a
    ``queue.Queue``: appends and pops are atomic under the interpreter
    lock, and a producer touches a lock only to wake a consumer that found
    the queue empty.  With a ``queue.Queue`` every ``put`` and ``get`` of
    every thread went through one mutex, and on a saturated interpreter a
    contended mutex costs each waiter a switch interval: a thousand groups'
    senders then spent their time queueing for the queue."""

    def __init__(self, size: int):
        self.size = size
        self._q: "collections.deque[Optional[Message]]" = collections.deque()
        self._wake = threading.Event()

    def put_nowait(self, m: Optional[Message]) -> None:
        if m is not None and len(self._q) >= self.size:
            raise queue.Full
        self._q.append(m)
        if not self._wake.is_set():
            self._wake.set()

    def get_nowait(self) -> Optional[Message]:
        try:
            return self._q.popleft()
        except IndexError:
            raise queue.Empty from None

    def get(self, timeout: float) -> Optional[Message]:
        while True:
            try:
                return self._q.popleft()
            except IndexError:
                pass
            self._wake.clear()
            if self._q:  # appended between the pop and the clear
                continue
            if not self._wake.wait(timeout):
                raise queue.Empty


class Transport:
    """Reference ``transport.go:156`` ``Transport``."""

    def __init__(
        self,
        source_address: str,
        deployment_id: int,
        registry: Registry,
        raft_rpc_factory: Callable[..., IRaftRPC],
        message_handler: Callable[[MessageBatch], None],
        snapshot_status_handler: Callable[[int, int, bool], None],
        unreachable_handler: Optional[Callable[[int, int], None]] = None,
        sys_events=None,
        snapshot_dir_fn: Optional[Callable[[int, int], str]] = None,
        max_send_queue_size: int = 0,
        snapshot_received_handler: Optional[Callable[[int, int, int], None]] = None,
        max_snapshot_send_bytes_per_second: int = 0,
        metrics_registry=None,
    ):
        self.source_address = source_address
        self.deployment_id = deployment_id
        self.registry = registry
        self.message_handler = message_handler
        self.snapshot_status_handler = snapshot_status_handler
        self.snapshot_received_handler = snapshot_received_handler
        self.unreachable_handler = unreachable_handler
        self.sys_events = sys_events
        self._mu = threading.Lock()
        self._queues: Dict[str, SendQueue] = {}
        self._breakers: Dict[str, CircuitBreaker] = {}
        self._stopped = threading.Event()
        self._queue_len = max_send_queue_size or Soft.send_queue_length
        # partition injection (monkey.go:82 transport drop-hook role):
        # addr -> blocked predicate, wired by the chaos harness (the fast
        # lane blocks its native streams itself; this filter covers the
        # paths that do NOT ride them — Python-socket sends, snapshot
        # jobs, inbound chunks and Python-received batches)
        self.partition_filter: Optional[Callable[[str], bool]] = None
        # per-peer RTT injection (ISSUE 10, transport/latency.py): the
        # per-remote sender thread sleeps the link's one-way delay before
        # each batch — that link gains latency while messages queued
        # during the sleep coalesce into the same batch (latency, not a
        # bandwidth collapse).  None (default) adds zero cost.
        self.latency = None
        self._snapshot_count_mu = threading.Lock()
        self._snapshot_jobs = 0
        from .bandwidth import TokenBucket
        from .metrics import TransportMetrics

        # the owning NodeHost's registry (ISSUE 14 satellite) — the
        # dragonboat_transport_* families then ride the same exposition
        # write_health_metrics and the /metrics endpoint serve
        self.metrics = TransportMetrics(registry=metrics_registry)
        # snapshot-plane bandwidth cap (reference tcp.go:430-437); 0 = off
        self.snapshot_bucket = TokenBucket(max_snapshot_send_bytes_per_second)
        from .chunks import Chunks

        def _snapshot_received(cluster_id, node_id, index, from_):
            self.metrics.snapshot_received()
            if self.sys_events is not None:
                from ..events import SystemEvent, SystemEventType

                self.sys_events.publish(
                    SystemEvent(
                        type=SystemEventType.SNAPSHOT_RECEIVED,
                        cluster_id=cluster_id,
                        node_id=node_id,
                        index=index,
                        from_=from_,
                    )
                )
            if self.snapshot_received_handler is not None:
                # ack the sender (SNAPSHOT_RECEIVED wire message) so its
                # feedback tracker releases the send status quickly
                self.snapshot_received_handler(cluster_id, node_id, from_)

        self.chunks = Chunks(
            deployment_id=deployment_id,
            snapshot_dir_fn=snapshot_dir_fn or (lambda c, n: ""),
            message_handler=message_handler,
            source_address=source_address,
            on_received=_snapshot_received,
        )
        self.rpc = raft_rpc_factory(
            source_address, self.handle_request, self._add_chunk_filtered
        )
        self.rpc.start()

    def _add_chunk_filtered(self, c) -> bool:
        """Inbound snapshot chunks from a partitioned sender are refused
        (False poisons the transfer connection — what a netsplit does)."""
        pf = self.partition_filter
        if pf is not None:
            addr = self.registry.resolve(c.cluster_id, c.from_)
            if addr is not None and pf(addr):
                return False
        ok = self.chunks.add_chunk(c)
        if ok:
            # count only ACCEPTED chunks (the family's HELP contract) —
            # a stale/out-of-order chunk add_chunk rejects must not
            # inflate the receive counter against the sender's
            self.metrics.snapshot_chunks_received()
        return ok

    # ---- send path ----

    def breaker(self, addr: str) -> CircuitBreaker:
        # lock-free on the send path (a dict get is atomic): the lock is
        # for the first message to a remote only
        b = self._breakers.get(addr)
        if b is None:
            with self._mu:
                b = self._breakers.setdefault(addr, CircuitBreaker())
        return b

    def send(self, m: Message) -> bool:
        addr = self.registry.resolve(m.cluster_id, m.to)
        if addr is None:
            return False
        return self.send_to_host(addr, m)

    def send_to_host(self, addr: str, m: Message) -> bool:
        """Queue ``m`` for the host at ``addr`` (the tail of ``send``; a
        host-addressed block message has no (cluster, node) to resolve)."""
        if self._stopped.is_set():
            return False
        pf = self.partition_filter
        if pf is not None and pf(addr):
            return False  # injected netsplit: unreachable
        b = self.breaker(addr)
        if not b.ready():
            return False
        # lock-free where the remote's queue exists (a send that races a
        # dying sender's removal of the queue loses its message, as one
        # queued a moment earlier would have been lost with it)
        sq = self._queues.get(addr)
        spawn = False
        if sq is None:
            with self._mu:
                sq = self._queues.get(addr)
                spawn = sq is None
                if spawn:
                    sq = SendQueue(self._queue_len)
                    self._queues[addr] = sq
        if spawn:
            t = threading.Thread(
                target=self._process_queue,
                args=(addr, sq),
                name=f"sender-{addr}",
                daemon=True,
            )
            t.start()
        try:
            sq.put_nowait(m)
            return True
        except queue.Full:
            self.metrics.message_dropped()
            return False

    def _process_queue(self, addr: str, sq: SendQueue) -> None:
        b = self.breaker(addr)
        conn = None
        try:
            conn = self.rpc.get_connection(addr)
            b.success()
            self._publish_conn_event(addr, failed=False)
            while not self._stopped.is_set():
                try:
                    m = sq.get(timeout=1.0)
                except queue.Empty:
                    continue
                if m is None:
                    return
                lat = self.latency
                if lat is not None:
                    # injected link delay (latency.py): sleep FIRST so
                    # everything arriving meanwhile rides this batch
                    d = lat.delay(self.source_address, addr)
                    if d > 0:
                        time.sleep(d)
                batch = MessageBatch(
                    requests=[m],
                    deployment_id=self.deployment_id,
                    source_address=self.source_address,
                )
                size = _msg_size(m)
                # batch everything already queued, up to the cap
                while size < Soft.max_message_batch_size:
                    try:
                        nxt = sq.get_nowait()
                    except queue.Empty:
                        break
                    if nxt is None:
                        return
                    batch.requests.append(nxt)
                    size += _msg_size(nxt)
                conn.send_message_batch(batch)
                self.metrics.message_sent(len(batch.requests))
                self.metrics.batch_sent(size)
        except (TransportError, OSError) as e:
            plog.warning("sender to %s failed: %s", addr, e)
            self.metrics.message_connection_failed()
            b.fail()
            self._publish_conn_event(addr, failed=True)
            self._notify_unreachable(addr)
        finally:
            if conn is not None:
                conn.close()
            with self._mu:
                self._queues.pop(addr, None)

    def _publish_conn_event(self, addr: str, failed: bool, snapshot: bool = False) -> None:
        if self.sys_events is None:
            return
        from ..events import SystemEvent, SystemEventType

        if snapshot:
            t = (
                SystemEventType.SEND_SNAPSHOT_ABORTED
                if failed
                else SystemEventType.SEND_SNAPSHOT_COMPLETED
            )
        else:
            t = (
                SystemEventType.CONNECTION_FAILED
                if failed
                else SystemEventType.CONNECTION_ESTABLISHED
            )
        self.sys_events.publish(SystemEvent(type=t, address=addr))

    def _notify_unreachable(self, addr: str) -> None:
        if self.unreachable_handler is None:
            return
        for cluster_id, node_id in self.registry.reverse_resolve(addr):
            self.unreachable_handler(cluster_id, node_id)

    # ---- snapshot send plane (reference snapshot.go/job.go) ----

    def send_snapshot(self, m: Message) -> bool:
        if m.type != MessageType.INSTALL_SNAPSHOT or m.snapshot is None:
            return False
        if self._stopped.is_set():
            return False
        addr = self.registry.resolve(m.cluster_id, m.to)
        if addr is None:
            return False
        pf = self.partition_filter
        if pf is not None and pf(addr):
            return False  # injected netsplit: snapshot path blocked too
        with self._snapshot_count_mu:
            if self._snapshot_jobs >= Soft.max_snapshot_connections:
                return False
            self._snapshot_jobs += 1
        t = threading.Thread(
            target=self._snapshot_job,
            args=(m, addr),
            name=f"snapshot-to-{addr}",
            daemon=True,
        )
        t.start()
        return True

    def _snapshot_job(self, m: Message, addr: str) -> None:
        from .snapshotsender import send_snapshot_chunks, split_snapshot_message

        failed = False
        conn = None
        if self.sys_events is not None:
            from ..events import SystemEvent, SystemEventType

            self.sys_events.publish(
                SystemEvent(
                    type=SystemEventType.SEND_SNAPSHOT_STARTED,
                    cluster_id=m.cluster_id,
                    node_id=m.to,
                    address=addr,
                )
            )
        try:
            chunks = split_snapshot_message(
                m, self.deployment_id, Soft.snapshot_chunk_size
            )
            conn = self.rpc.get_snapshot_connection(addr)
            send_snapshot_chunks(
                conn, chunks, self._stopped, bucket=self.snapshot_bucket
            )
            self.metrics.snapshot_sent()
            self.metrics.snapshot_chunks_sent(len(chunks))
        except (TransportError, OSError, RuntimeError) as e:
            plog.warning("snapshot send to %s failed: %s", addr, e)
            self.metrics.snapshot_connection_failed()
            failed = True
        finally:
            if conn is not None:
                conn.close()
            with self._snapshot_count_mu:
                self._snapshot_jobs -= 1
        self._publish_conn_event(addr, failed=failed, snapshot=True)
        self.snapshot_status_handler(m.cluster_id, m.to, failed)

    # ---- streaming plane (reference GetStreamSink snapshot.go:65) ----

    def get_stream_sink(self, cluster_id: int, node_id: int):
        """A Sink streaming chunks to ``(cluster_id, node_id)`` over a
        dedicated connection, or None when unreachable/at capacity."""
        from .job import Sink, StreamJob

        if self._stopped.is_set():
            return None
        addr = self.registry.resolve(cluster_id, node_id)
        if addr is None:
            return None
        b = self.breaker(addr)
        if not b.ready():
            return None
        with self._snapshot_count_mu:
            if self._snapshot_jobs >= Soft.max_concurrent_streaming_snapshots:
                return None
            self._snapshot_jobs += 1
        if self.sys_events is not None:
            from ..events import SystemEvent, SystemEventType

            self.sys_events.publish(
                SystemEvent(
                    type=SystemEventType.SEND_SNAPSHOT_STARTED,
                    cluster_id=cluster_id,
                    node_id=node_id,
                    address=addr,
                )
            )

        def on_done(cid, nid, failed):
            with self._snapshot_count_mu:
                self._snapshot_jobs -= 1
            if failed:
                b.fail()
                self.metrics.snapshot_connection_failed()
            else:
                b.success()
                self.metrics.snapshot_sent()
            self._publish_conn_event(addr, failed=failed, snapshot=True)
            self.snapshot_status_handler(cid, nid, failed)

        job = StreamJob(
            self.rpc, addr, cluster_id, node_id, on_done,
            bucket=self.snapshot_bucket,
        )
        return Sink(job)

    # ---- receive path ----

    def handle_request(self, batch: MessageBatch) -> None:
        """Reference ``transport.go:289`` ``handleRequest``: filter by
        deployment id, then hand to the nodehost message router."""
        if batch.deployment_id != self.deployment_id:
            plog.warning(
                "dropped batch from %s: deployment id %d != %d",
                batch.source_address,
                batch.deployment_id,
                self.deployment_id,
            )
            self.metrics.message_receive_dropped(len(batch.requests))
            return
        pf = self.partition_filter
        if pf is not None and batch.source_address and pf(batch.source_address):
            self.metrics.message_receive_dropped(len(batch.requests))
            return  # injected netsplit: Python-received batch dropped
        self.metrics.message_received(len(batch.requests))
        self.metrics.batch_received(sum(_msg_size(m) for m in batch.requests))
        self.message_handler(batch)

    def tick(self) -> None:
        self.chunks.tick()

    def stop(self) -> None:
        self._stopped.set()
        with self._mu:
            queues = list(self._queues.values())
        for sq in queues:
            try:
                sq.put_nowait(None)
            except queue.Full:
                pass
        self.rpc.stop()


def _msg_size(m: Message) -> int:
    return 64 + sum(len(e.cmd) + 48 for e in m.entries)


def create_transport(
    nhconfig,
    registry: Registry,
    message_handler,
    snapshot_status_handler,
    unreachable_handler=None,
    snapshot_dir_fn=None,
    sys_events=None,
    snapshot_received_handler=None,
    metrics_registry=None,
) -> Transport:
    """Reference ``nodehost.go:1677`` ``createTransport``: pick the RPC module
    from config (factory override, else TCP; chan under in-memory test runs)."""
    factory = nhconfig.raft_rpc_factory
    if factory is None:
        from .tcp import TCPTransport

        def factory(addr, rh, ch):
            return TCPTransport(
                addr,
                rh,
                ch,
                listen_address=nhconfig.get_listen_address(),
                mutual_tls=nhconfig.mutual_tls,
                ca_file=nhconfig.ca_file,
                cert_file=nhconfig.cert_file,
                key_file=nhconfig.key_file,
            )

    return Transport(
        source_address=nhconfig.raft_address,
        deployment_id=nhconfig.get_deployment_id(),
        registry=registry,
        raft_rpc_factory=factory,
        message_handler=message_handler,
        snapshot_status_handler=snapshot_status_handler,
        unreachable_handler=unreachable_handler,
        snapshot_dir_fn=snapshot_dir_fn,
        max_send_queue_size=nhconfig.max_send_queue_size,
        sys_events=sys_events,
        snapshot_received_handler=snapshot_received_handler,
        max_snapshot_send_bytes_per_second=(
            nhconfig.max_snapshot_send_bytes_per_second
        ),
        metrics_registry=metrics_registry,
    )
