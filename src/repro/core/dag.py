"""Region-granularity DAG scheduling: bounded edge queues + commit gates.

The barrier orchestrator runs stages sequentially with a fully materialized
intermediate between every pair, so a multi-stage job pays the *sum* of stage
wall times.  This module provides the machinery that lets connected stages
stream into each other at **region granularity** (the paper's §IV.C
"orchestration of multiple connected pipelines", at the granularity the
workflow-design studies in PAPERS.md found decisive for satellite imagery):

  * :class:`EdgeQueue` — one producer→consumer edge.  The producer's
    write-behind reports **committed** row extents (rows whose bytes a
    ``pwrite``/flush actually put on disk — not rows merely buffered in the
    :class:`~repro.raster.io.StripWriter` coalescing run); the consumer
    derives per-region readiness from the committed coverage
    (:class:`~repro.core.splitting.RowCoverage`).  A bounded number of
    committed-but-unreleased strips (``capacity``) applies backpressure to
    the producer, and failures propagate in both directions instead of
    wedging either side.
  * :class:`EdgeFanout` — the producer-side sink a writer mapper binds to:
    it fans ``offer`` (flow control, before the write) and ``commit`` (after
    the bytes are on disk) out to every outgoing edge.
  * :class:`RegionGate` — the consumer-side gate the streaming executors
    accept: given a region's :class:`~repro.core.execplan.PlanDescription`
    it blocks until the **exact input rows the region reads** (halos and
    windowed reads included — the describe pass records them) are committed
    upstream, and releases them when the region completes.

Deadlock freedom
----------------

Backpressure yields to *unmet demand*: a producer blocked at ``capacity``
proceeds (counted as an ``overdraft``) exactly while some consumer is
blocked waiting for rows **no offered strip covers** — rows the queue's
in-flight strips cannot possibly satisfy, e.g. a halo read past the
frontier at ``capacity=1``, or a whole-image consumer region.  A consumer
blocked on rows that *are* offered needs no overdraft: offered strips are
written unconditionally once their offer returns, and the waiting consumer
re-runs the producer writer's flush on every poll, so buffered-but-
uncommitted coalesced rows always reach disk without producer progress.
Backpressure only engages while a region-granular consumer is attached
(``consumer_started`` — the pipelined orchestrator arms it at edge creation
for pool consumers and never for stage-granularity SPMD consumers).  A
blocked producer therefore always implies a consumer that is processing
ready regions and will release capacity, and a blocked consumer either
drains committed/offered rows via flush or lifts the producer past the
bound — there is no cycle.  A producer with several outgoing edges
(:class:`EdgeFanout`) admits each strip to all of them at once, and proceeds
while any of them has unmet demand: a strip offered on one edge but held
back by another would be neither written nor counted as unmet.  Waits
additionally poll with a short timeout as
a belt-and-braces guard, and every failure path wakes all sleepers.

When the producer offers strips in consumer (row) order — the pipelined
orchestrator forces FIFO hand-out on producer stages for exactly this
reason — overdrafts stay rare (zero for halo-free graphs at
``capacity >= 2``) and ``max_in_flight`` stays at ``capacity``; an
out-of-order producer keeps liveness but may transiently exceed the bound
while a demanded row waits for its strip to be offered.

Failure propagation
-------------------

A failed producer marks its outgoing edges with the original exception;
blocked consumers raise :class:`UpstreamFailed` carrying that original
exception (``.cause``) instead of hanging.  A global cancel (a failed
sibling stage, or :meth:`Orchestrator.cancel`) marks every edge with
:class:`PipelineCancelled`; blocked producers and consumers alike unwind
promptly.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.core.execplan import PlanDescription
from repro.core.region import ImageRegion
from repro.core.splitting import RowCoverage

#: belt-and-braces poll period for blocked waits — all state transitions
#: notify the condition, so this only bounds the damage of a missed wakeup
_POLL_S = 0.1


class PipelineCancelled(RuntimeError):
    """The pipelined run was aborted (failed sibling stage or user cancel)."""


class UpstreamFailed(RuntimeError):
    """A producer stage failed; its consumers cancel with the original error.

    ``stage`` names the failed producer and ``cause`` is the original
    exception (never another :class:`UpstreamFailed` — nesting is unwrapped
    at raise time, so a chain failure surfaces the root cause everywhere).
    """

    def __init__(self, stage: str, cause: BaseException):
        while isinstance(cause, UpstreamFailed):
            stage, cause = cause.stage, cause.cause
        super().__init__(f"upstream stage {stage!r} failed: {cause!r}")
        self.stage = stage
        self.cause = cause


@dataclasses.dataclass
class EdgeStats:
    """Counters for one edge of a pipelined run.

    ``max_in_flight`` is the peak number of producer strips offered but not
    yet released by the consumer — the bound the queue capacity enforces
    while a region-granular consumer is attached.  ``overdrafts`` counts
    offers that proceeded past capacity because a consumer was blocked
    waiting for rows no offered strip covers (unmet demand overrides pacing
    — see the module docstring's deadlock-freedom argument).
    """

    commits: int = 0
    offers: int = 0
    waits: int = 0
    releases: int = 0
    overdrafts: int = 0
    max_in_flight: int = 0


class EdgeQueue:
    """Bounded region queue on one producer→consumer stage edge."""

    def __init__(self, producer: str, consumer: str, capacity: int = 2):
        if capacity < 1:
            raise ValueError("queue capacity must be >= 1")
        self.producer = producer
        self.consumer = consumer
        self.capacity = capacity
        self.stats = EdgeStats()
        self._cv = threading.Condition()
        self._rows: Optional[int] = None  # total output rows, set at open
        self._committed = RowCoverage()
        self._offered = RowCoverage()  # rows whose offer returned (write follows)
        self._released = RowCoverage()
        #: offered-but-unreleased strips, FIFO by offer order
        self._tokens: "collections.deque[Tuple[int, int]]" = collections.deque()
        self._opened = False
        self._producer_done = False
        self._consumer_active = False  # a region-granular consumer is pulling
        self._consumer_done = False
        self._failure: Optional[BaseException] = None
        self._failed_stage: Optional[str] = None  # None → global cancel
        self._flush_cb: Optional[Callable[[], None]] = None
        #: row ranges consumers are currently blocked on in wait_rows
        self._wait_demands: List[List[int]] = []

    # -- failure/cancel (either side, or the orchestrator) ---------------------
    def fail(self, stage: str, exc: BaseException) -> None:
        """Mark the edge failed by ``stage`` (the producer); wake everyone."""
        with self._cv:
            if self._failure is None:
                self._failure, self._failed_stage = exc, stage
            self._cv.notify_all()

    def cancel(self, exc: BaseException) -> None:
        """Global abort: wake everyone with :class:`PipelineCancelled`.  An
        edge already failed keeps its more specific producer failure."""
        with self._cv:
            if self._failure is None:
                self._failure, self._failed_stage = exc, None
            self._cv.notify_all()

    def _raise_if_failed_locked(self) -> None:
        if self._failure is None:
            return
        if self._failed_stage is not None:
            raise UpstreamFailed(self._failed_stage, self._failure)
        raise PipelineCancelled(
            f"edge {self.producer!r}→{self.consumer!r} cancelled"
        ) from self._failure

    # -- producer side ---------------------------------------------------------
    def open(self, rows: int) -> None:
        """The producer's output file exists (header written): consumers may
        build their readers now."""
        with self._cv:
            self._rows = int(rows)
            self._opened = True
            self._cv.notify_all()

    def set_flush(self, cb: Callable[[], None]) -> None:
        """Register the producer writer's flush so a waiting consumer can
        force buffered-but-uncommitted coalesced rows onto disk."""
        with self._cv:
            self._flush_cb = cb

    def _unmet_demand_locked(self) -> bool:
        """True when a blocked consumer demands rows no offered strip covers
        — rows the in-flight window cannot satisfy without this (or a later)
        offer proceeding."""
        return any(
            not self._offered.covers(lo, hi) for lo, hi in self._wait_demands
        )

    def offer(self, region: ImageRegion) -> None:
        """Flow control, called by the producer *before* writing ``region``.

        Blocks while ``capacity`` strips are in flight **and** a
        region-granular consumer is attached and making progress on the
        offered rows; a consumer blocked on rows *beyond* every offered
        strip lifts the backpressure (overdraft) so the pipeline can never
        cycle-wait.  Raises when the run was cancelled.
        """
        with self._cv:
            self._raise_if_failed_locked()
            while self._full_locked() and not self._unmet_demand_locked():
                self._cv.wait(_POLL_S)
                self._raise_if_failed_locked()
            self._admit_locked(region)

    def full(self) -> bool:
        """``capacity`` strips are in flight to a region-granular consumer
        that is still running.  Raises when the run was cancelled."""
        with self._cv:
            self._raise_if_failed_locked()
            return self._full_locked()

    def starved(self) -> bool:
        """A blocked consumer demands rows no offered strip covers."""
        with self._cv:
            return self._unmet_demand_locked()

    def wait_change(self) -> None:
        """Sleep until this edge's state changes, at most one poll period."""
        with self._cv:
            self._cv.wait(_POLL_S)

    def admit(self, region: ImageRegion) -> None:
        """Put ``region`` in flight without waiting (the caller applied the
        flow control: :meth:`EdgeFanout.offer`)."""
        with self._cv:
            self._raise_if_failed_locked()
            self._admit_locked(region)

    def _full_locked(self) -> bool:
        return (
            self._consumer_active
            and not self._consumer_done
            and len(self._tokens) >= self.capacity
        )

    def _admit_locked(self, region: ImageRegion) -> None:
        if region.col0 != 0:
            raise ValueError(
                f"edge {self.producer!r}→{self.consumer!r}: pipelined "
                "producers must write full-width strips (row-granularity "
                "commit protocol); got a tile split — use barrier mode "
                "or a stripe splitter"
            )
        self.stats.offers += 1
        if self._full_locked():
            self.stats.overdrafts += 1
        self._tokens.append((region.row0, region.row1))
        self._offered.add(region.row0, region.row1)
        self.stats.max_in_flight = max(
            self.stats.max_in_flight, len(self._tokens)
        )
        self._cv.notify_all()  # waiters re-check offered coverage

    def commit(self, row0: int, row1: int) -> None:
        """Rows ``[row0, row1)`` are on disk (called post-``pwrite``/flush by
        the producer's :class:`~repro.raster.io.StripWriter`)."""
        with self._cv:
            self._committed.add(row0, row1)
            self.stats.commits += 1
            self._cv.notify_all()

    def close_producer(self) -> None:
        """The producer stage completed: all rows are committed."""
        with self._cv:
            if self._rows is not None:
                self._committed.add(0, self._rows)
            self._producer_done = True
            self._cv.notify_all()

    # -- consumer side ---------------------------------------------------------
    def wait_open(self, timeout: Optional[float] = None) -> None:
        with self._cv:
            waited = 0.0
            while not self._opened:
                self._raise_if_failed_locked()
                self._cv.wait(_POLL_S)
                waited += _POLL_S
                if timeout is not None and waited >= timeout:
                    raise TimeoutError(
                        f"edge {self.producer!r}→{self.consumer!r}: producer "
                        f"never opened within {timeout}s"
                    )
            self._raise_if_failed_locked()

    def consumer_started(self) -> None:
        """A region-granular consumer is attached: engage backpressure."""
        with self._cv:
            self._consumer_active = True
            self._cv.notify_all()

    def consumer_finished(self) -> None:
        """The consumer stage completed: lift backpressure for good."""
        with self._cv:
            self._consumer_done = True
            self._tokens.clear()
            self._cv.notify_all()

    def wait_rows(self, row0: int, row1: int) -> None:
        """Block until rows ``[row0, row1)`` are committed upstream (clamped
        to the producer's real rows).  Raises :class:`UpstreamFailed` /
        :class:`PipelineCancelled` instead of hanging on a dead producer.

        While blocked, the demand is registered so producer offers covering
        rows beyond the offered frontier can overdraft past capacity, and
        the producer writer's flush is re-run on **every** poll — rows whose
        write landed in the coalescing buffer after our previous flush still
        reach disk without any further producer progress."""
        if self._rows is not None:
            row0, row1 = max(0, row0), min(self._rows, row1)
        if row1 <= row0:
            return
        demand = [row0, row1]
        with self._cv:
            self._raise_if_failed_locked()
            if self._committed.covers(row0, row1):
                return
            self.stats.waits += 1
            self._wait_demands.append(demand)
            self._cv.notify_all()  # wake a producer blocked on backpressure
        try:
            while True:
                # flush OUTSIDE the edge lock: the writer's commit hook runs
                # under the writer lock and takes this edge's lock, so
                # holding it here would invert the lock order
                flush = self._flush_cb
                if flush is not None:
                    try:
                        flush()  # force coalesced-but-unflushed rows to disk
                    except Exception:
                        pass  # advisory only — the writer may be mid-close
                with self._cv:
                    if self._committed.covers(row0, row1):
                        return
                    self._raise_if_failed_locked()
                    if self._producer_done:
                        raise RuntimeError(
                            f"edge {self.producer!r}→{self.consumer!r}: "
                            f"producer completed without committing rows "
                            f"[{row0}, {row1}) — commit hook not wired?"
                        )
                    self._cv.wait(_POLL_S)
                    if self._committed.covers(row0, row1):
                        return
                    self._raise_if_failed_locked()
        finally:
            with self._cv:
                self._wait_demands.remove(demand)
                self._cv.notify_all()

    def release(self, row0: int, row1: int) -> None:
        """The consumer finished a region that read rows ``[row0, row1)``:
        retire covered in-flight strips (frees producer capacity).  Purely a
        pacing signal — the data stays on disk for later overlapping reads."""
        with self._cv:
            self._released.add(row0, row1)
            self.stats.releases += 1
            if self._tokens:
                self._tokens = collections.deque(
                    t for t in self._tokens if not self._released.covers(*t)
                )
            self._cv.notify_all()

    def wait_complete(self, timeout: Optional[float] = None) -> None:
        """Block until the producer stage completed (stage-granularity
        consumers, e.g. an SPMD stage that reads its whole input up front)."""
        with self._cv:
            waited = 0.0
            while not self._producer_done:
                self._raise_if_failed_locked()
                self._cv.wait(_POLL_S)
                waited += _POLL_S
                if timeout is not None and waited >= timeout:
                    raise TimeoutError(
                        f"edge {self.producer!r}→{self.consumer!r}: producer "
                        f"did not complete within {timeout}s"
                    )
            self._raise_if_failed_locked()

    @property
    def in_flight(self) -> int:
        with self._cv:
            return len(self._tokens)


class EdgeFanout:
    """Producer-side sink: fans writer events out to every outgoing edge.

    Bound to the stage's writer mapper
    (:meth:`~repro.raster.mappers.ParallelRasterWriter.bind_commit_sink`):
    ``offer`` applies flow control before each strip write, ``commit`` fires
    from the :class:`~repro.raster.io.StripWriter` commit hook after the
    bytes land on disk, ``opened``/``set_flush`` wire the begin/flush
    lifecycle.
    """

    def __init__(self, edges: Sequence[EdgeQueue]):
        self.edges = list(edges)

    def opened(self, info) -> None:
        for e in self.edges:
            e.open(info.rows)

    def set_flush(self, cb: Callable[[], None]) -> None:
        for e in self.edges:
            e.set_flush(cb)

    def offer(self, region: ImageRegion) -> None:
        """Admit ``region`` to every edge at once, when no edge is at
        capacity or some edge's consumer waits on rows no offered strip
        covers.  Offered edge by edge, its rows would read as offered on one
        edge while another edge held the write back, and a consumer of the
        first that waits behind a consumer of the second would wait for
        ever."""
        while True:
            full = [e for e in self.edges if e.full()]
            if not full or any(e.starved() for e in self.edges):
                break
            full[0].wait_change()
        for e in self.edges:
            e.admit(region)

    def commit(self, row0: int, row1: int) -> None:
        for e in self.edges:
            e.commit(row0, row1)

    def close(self) -> None:
        for e in self.edges:
            e.close_producer()

    def fail(self, stage: str, exc: BaseException) -> None:
        for e in self.edges:
            e.fail(stage, exc)


class RegionGate:
    """Consumer-side region-availability gate for the streaming executors.

    ``wait(desc)`` blocks until every input row the described region actually
    reads — the describe pass records the exact (halo- and window-inclusive)
    source requests — is committed on its edge; ``done(desc)`` releases those
    rows after the region's output is consumed.  Sources whose ``path`` is
    not a gated edge (side inputs that already exist in full) pass through
    ungated.
    """

    def __init__(self, edges_by_path: Dict[str, EdgeQueue]):
        self.edges_by_path = dict(edges_by_path)

    def _needs(self, desc: PlanDescription) -> List[Tuple[EdgeQueue, int, int]]:
        needs = []
        for source, clamped, _requested in desc.reads:
            edge = self.edges_by_path.get(getattr(source, "path", None))
            if edge is None:
                continue
            full = source.output_info().full_region
            r0 = max(0, clamped.row0)
            r1 = min(full.rows, clamped.row1)
            if r1 > r0:
                needs.append((edge, r0, r1))
        return needs

    def wait(self, desc: PlanDescription) -> None:
        for edge, r0, r1 in self._needs(desc):
            edge.wait_rows(r0, r1)

    def done(self, desc: PlanDescription) -> None:
        for edge, r0, r1 in self._needs(desc):
            edge.release(r0, r1)
