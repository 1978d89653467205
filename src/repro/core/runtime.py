"""Async pane-pipelined streaming driver: the execution layer over sessions.

The paper's latency claim (§5.2) hinges on the edge node *overlapping* its
three per-pane phases — arrival, host→device staging, fused edge compute —
instead of summing them.  A bare :class:`~.session.StreamSession` is
synchronous pane-at-a-time: ``step`` is async-dispatch-friendly (it never
blocks on the device), but whoever drives it still interleaves ingest and
compute on one thread.  :class:`StreamRuntime` is that driver done right:

  * a **producer thread** pulls panes from a pluggable :class:`Source`
    (any iterable of ``WindowBatch`` — the ``data/streams.py`` generators
    via ``pane_windows``, or a bursty simulator in tests) into a
    :class:`~.qdisc.BoundedPaneQueue`;
  * the **pane loop** double-buffers staging: pane k+1 is ``jax.device_put``
    while pane k's fused edge program runs — and *never* calls
    ``block_until_ready`` / ``.item()`` / ``device_get`` (edgelint EDG002
    polices ``run``/``process``/``_consume``/``_dispatch`` un-suppressed);
    the only blocking sync lives in :meth:`_retire`, which waits on a pane
    that is ``max_inflight`` dispatches old — i.e. almost always already
    finished — to bound the in-flight window and timestamp completions;
  * **backpressure** sheds at the queue (drop-newest/drop-oldest) and the
    shed tuples flow into the existing accounting chain: they are attached
    to the next admitted pane's ``n_dropped``/``drop_causes`` and surface in
    ``QueryResult.n_dropped`` and the session's ``total_dropped_by_cause``;
  * **event-driven sampling** (:class:`~.feedback.EventPolicy`): watched
    registrations decay to an idle fraction while their per-stratum means
    are stable and snap to a hot fraction on a shift or heartbeat — the
    change score is computed lazily on-device and read back one pane late
    (:meth:`_read_score`), so quiet regions cost ~nothing and the readback
    never stalls the dispatch stream;
  * **load shedding**: when queue depth crosses ``shed_highwater`` the
    runtime scales every registration's fraction by ``shed_fraction_scale``
    (floored at ``shed_min_fraction``) and optionally decimates arrivals
    (deterministic 1-in-k, cause ``shed``); it restores fractions when the
    queue falls below ``shed_lowwater`` — degrade, never crash;
  * **drain-then-snapshot checkpointing**: :meth:`checkpoint` first
    processes every queued/staged pane, then snapshots the session, so a
    restore resumes bit-identically to an uninterrupted run even when the
    ingest queue was non-empty at snapshot time;
  * :class:`RuntimeStats` observability: per-pane ingest/stage/dispatch
    latency percentiles, queue high-water mark, drops by cause, overlap
    efficiency (compute-busy wall fraction), and the totals of the host
    spans and counters (:mod:`.spans`) recorded on the pane loop and the
    producer thread while this runtime drives them.

Determinism: the runtime derives pane k's PRNG key as
``jax.random.fold_in(root_key, k)`` (the checkpoint-replay discipline), so
with a lossless queue policy (``"block"``) its estimates are bit-identical
to a synchronous ``session.step`` loop over the same panes.  The clock is
injectable (``RuntimeConfig.clock``) and everything else is
arrival-order-deterministic — no RNG, keeping the core closure EDG001-clean.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import threading
import time
from typing import Callable, Iterator, Protocol, runtime_checkable

import jax
import numpy as np

from . import feedback, spans
from .feedback import EventPolicy, EventState
from .qdisc import BoundedPaneQueue, DropLedger, QueueClosed
from .windows import WindowBatch


@runtime_checkable
class Source(Protocol):
    """Anything the producer thread can iterate for panes.

    The existing window iterators (``pane_windows``/``count_windows``/
    ``time_windows`` over ``data/streams.py`` generators) already satisfy
    this; ``data/sources.py`` adds paced/bursty arrival simulators for
    tests and benchmarks.
    """

    def __iter__(self) -> Iterator[WindowBatch]: ...


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Knobs of the driver; defaults favor throughput with bounded memory.

    ``clock`` is an injectable monotonic timer (tests freeze it); the
    default is the uncalled ``time.perf_counter`` reference — the runtime
    itself never reads a wall clock except through this hook.
    """

    queue_capacity: int = 8
    policy: str = "drop-newest"  # see qdisc.QUEUE_POLICIES
    max_inflight: int = 2  # dispatched-but-unretired panes kept in flight
    stage_flush_s: float = 0.002  # max time a staged pane waits for a successor
    load_shedding: bool = False  # opt-in: degrade fractions under saturation
    shed_highwater: float = 0.75  # queue fill ratio entering shed mode
    shed_lowwater: float = 0.25  # queue fill ratio leaving shed mode
    shed_fraction_scale: float = 0.5  # fraction multiplier while shedding
    shed_min_fraction: float = 0.05
    shed_decimate: int = 0  # while shedding admit 1 of every k panes (0=off)
    clock: Callable[[], float] = time.perf_counter


_END = object()  # the producer's end-of-source marker


@dataclasses.dataclass
class _Arrival:
    """A pane plus its producer-side timestamps, as queued.

    Exposes ``size``/``drop_causes`` so the queue's drop accounting reads
    through to the wrapped pane.
    """

    pane: WindowBatch
    t_enqueue: float
    ingest_s: float  # producer time spent obtaining this pane from the source

    @property
    def size(self) -> int:
        return getattr(self.pane, "size", 0)

    @property
    def drop_causes(self) -> dict:
        return getattr(self.pane, "drop_causes", {}) or {}


@dataclasses.dataclass
class _Staged:
    arrival: _Arrival
    pane: WindowBatch  # columns already on device (jax.device_put issued)
    t_dequeue: float
    t_staged: float


@dataclasses.dataclass
class _InFlight:
    pane_index: int
    arrival: _Arrival
    t_dequeue: float
    t_staged: float
    t_dispatch: float
    t_dispatched: float
    markers: object  # pytree whose leaves complete when the pane is done


@dataclasses.dataclass
class PaneTiming:
    """Completed-pane timing record (all seconds, runtime clock)."""

    pane_index: int
    ingest_s: float  # producer: source iteration time for this pane
    queue_wait_s: float  # enqueue -> dequeue (end of runtime.queue_get)
    stage_s: float  # dequeue -> device_put issued (end of runtime.stage)
    dispatch_s: float  # the runtime.dispatch span: events, shedding, session.step
    latency_s: float  # enqueue -> retired (end-to-end pane latency)
    t_dispatch: float
    t_retired: float


def _percentiles(values_s) -> dict:
    if not values_s:
        return {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0}
    arr = np.asarray(values_s, np.float64) * 1e3
    return {
        "p50_ms": float(np.percentile(arr, 50)),
        "p95_ms": float(np.percentile(arr, 95)),
        "p99_ms": float(np.percentile(arr, 99)),
        "max_ms": float(arr.max()),
    }


@dataclasses.dataclass
class RuntimeStats:
    """Observability snapshot of one runtime (see :meth:`StreamRuntime.stats`).

    ``overlap_efficiency`` is compute-busy wall fraction: the union of the
    in-flight intervals [dispatch, retire] over the span from first dispatch
    to last retire — 1.0 means the device never waited on ingest.

    ``spans`` is ``{name: {"count", "total_s", "self_s"}}`` and ``counters``
    ``{name: int}``: what the runtime's :class:`~.spans.Recorder` saw on the
    pane loop and the producer thread (``runtime.*``, ``session.*``,
    ``compiles.<span>``, ``cache_loads.<span>``).
    """

    panes_processed: int
    panes_enqueued: int
    tuples_processed: int
    queue_depth_high_water: int
    dropped_tuples_by_cause: dict
    dropped_panes_by_cause: dict
    shed_panes: int
    overlap_efficiency: float
    wall_s: float
    ingest: dict
    queue_wait: dict
    stage: dict
    dispatch: dict
    pane_latency: dict
    # pipeline compiled-program cache counters (per jit family hit/miss plus
    # the aggregate compile_count) — the multi-tenant churn contract's
    # observability surface; empty when the session exposes no pipeline
    compile_cache: dict = dataclasses.field(default_factory=dict)
    # uplink byte accounting: cumulative comm_bytes (measured encoded bytes
    # when an uplink codec is configured, the analytic dense model otherwise)
    # plus the codec fingerprint the figure was measured under (None = dense)
    uplink: dict = dataclasses.field(default_factory=dict)
    spans: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)

    @property
    def dropped_tuples(self) -> int:
        return sum(self.dropped_tuples_by_cause.values())


def _overlap_efficiency(timings) -> float:
    """Union of [t_dispatch, t_retired] intervals / overall wall."""
    if not timings:
        return 0.0
    spans = sorted((t.t_dispatch, t.t_retired) for t in timings)
    wall = max(hi for _, hi in spans) - spans[0][0]
    if wall <= 0.0:
        return 1.0
    busy = 0.0
    cur_lo, cur_hi = spans[0]
    for lo, hi in spans[1:]:
        if lo > cur_hi:
            busy += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    busy += cur_hi - cur_lo
    return busy / wall


class StreamRuntime:
    """Producer/consumer driver running a :class:`~.session.StreamSession`.

    Typical use::

        sess = StreamSession(pipe)
        sess.register(query, window=WindowSpec())
        rt = StreamRuntime(sess, key=jax.random.key(0),
                           config=RuntimeConfig(policy="drop-oldest"))
        history = rt.run(pane_windows(stream, pane_tuples=20_000))
        rt.stats().pane_latency["p99_ms"], rt.stats().dropped_tuples_by_cause

    Incremental (single-threaded, deterministic) use::

        rt.offer(pane)          # enqueue without a producer thread
        rt.process()            # consume whatever is queued, no waiting
        rt.drain()              # flush staged + retire everything in flight
        rt.checkpoint(path)     # drain-then-snapshot
    """

    def __init__(self, session, key=None, config: RuntimeConfig | None = None):
        self.session = session
        self.config = config or RuntimeConfig()
        self.queue = BoundedPaneQueue(self.config.queue_capacity, self.config.policy)
        self._clock = self.config.clock
        self._recorder = spans.Recorder(self._clock)
        self._root_key = key
        self._history: list = []
        self._timings: list[PaneTiming] = []
        self._inflight: collections.deque[_InFlight] = collections.deque()
        self._staged: _Staged | None = None
        self._producer: threading.Thread | None = None
        self._producer_error: Exception | None = None
        self._watches: dict[int, tuple] = {}  # qid -> (reg, policy, column, state)
        self._pending_scores: list = []  # (reg, lazy score, matured-at pane)
        self._prev_means: dict[int, object] = {}  # qid -> last pane's mean vector
        self._shed_saved: dict[int, float] | None = None
        self.shed_panes = 0
        self._n_tuples = 0
        self._t_first: float | None = None
        self._t_last: float | None = None

    # -- event-driven sampling ----------------------------------------------

    def watch(self, reg, policy: EventPolicy | None = None, column: str | None = None):
        """Enable heartbeat + change-triggered fraction control for ``reg``.

        ``column`` defaults to the plan's first column; its per-stratum
        moment means drive the change score.  Incompatible with an SLO on
        the same registration only in the sense that both write
        ``reg.fraction`` — last writer (the SLO controller runs inside
        ``session.step``, the event hook just before the *next* dispatch)
        wins; in practice watched queries are registered without an SLO.
        """
        column = column or reg.plan.columns[0]
        self._watches[reg.qid] = (reg, policy or EventPolicy(), column, EventState())
        return self

    def _queue_events(self, _step) -> None:
        """After a dispatch: enqueue lazy change scores for watched regs.

        The score compares this pane's per-stratum moment means to the
        previous pane's — both device-resident; nothing syncs here.
        """
        for qid, (reg, policy, column, state) in self._watches.items():
            if not reg.ring:
                continue
            stats = reg.ring[-1].stats.get(column)
            moments = stats.get("moments") if stats else None
            if moments is None:
                continue
            prev = self._prev_means.get(qid)
            self._prev_means[qid] = moments.mean
            if prev is not None:
                score = feedback.change_score(prev, moments.mean)
                self._pending_scores.append((reg, policy, state, score))

    def _read_score(self, score) -> float:
        """The event loop's single sync point, one pane late by design: the
        score was dispatched a full pane ago and is all but guaranteed
        materialized, so this readback does not stall the stream."""
        return float(jax.device_get(score))

    def _apply_events(self) -> None:
        """Before the next dispatch: apply matured (pane-old) scores."""
        pending, self._pending_scores = self._pending_scores, []
        for reg, policy, state, score in pending:
            reg.fraction = feedback.event_fraction(
                state, self._read_score(score), reg.fraction, policy
            )

    # -- load shedding -------------------------------------------------------

    def _maybe_shed(self) -> None:
        cfg = self.config
        if not cfg.load_shedding:
            return
        depth = self.queue.depth
        hi = math.ceil(cfg.shed_highwater * self.queue.capacity)
        lo = math.floor(cfg.shed_lowwater * self.queue.capacity)
        if self._shed_saved is None and depth >= hi:
            self._shed_saved = {}
            for reg in self.session.registrations:
                self._shed_saved[reg.qid] = reg.fraction
                reg.fraction = max(
                    cfg.shed_min_fraction, reg.fraction * cfg.shed_fraction_scale
                )
            if cfg.shed_decimate > 1:
                self.queue.set_decimation(cfg.shed_decimate)
        elif self._shed_saved is not None and depth <= lo:
            for reg in self.session.registrations:
                saved = self._shed_saved.get(reg.qid)
                if saved is not None:
                    # never leave a fraction *below* its pre-shed value on
                    # account of shedding; controllers may have moved it up
                    reg.fraction = max(reg.fraction, saved)
            self._shed_saved = None
            self.queue.set_decimation(0)
        if self._shed_saved is not None:
            self.shed_panes += 1

    @property
    def shedding(self) -> bool:
        return self._shed_saved is not None

    # -- producer ------------------------------------------------------------

    def offer(self, pane, timeout: float | None = None) -> bool:
        """Enqueue one pane (producer side); returns True iff admitted."""
        t = self._clock()
        return self.queue.put(_Arrival(pane, t, 0.0), timeout=timeout)

    def _pump(self, source: Source) -> None:
        with self._recorder.active():
            try:
                panes = iter(source)
                while True:
                    with spans.span("runtime.source") as got:
                        pane = next(panes, _END)
                    if pane is _END:
                        break
                    with spans.span("runtime.queue_put") as put:
                        self.queue.put(_Arrival(pane, put.t0, got.t1 - got.t0))
            except QueueClosed:
                return  # the consumer stopped early and closed the queue
            except Exception as e:  # the thread's boundary: run() re-raises it
                self._producer_error = e
            finally:
                if not self.queue.closed:
                    self.queue.close()

    # -- the pane loop (EDG002-policed: no host syncs here) ------------------

    def run(self, source: Source, key=None, max_panes: int | None = None) -> list:
        """Drive the session over ``source`` with a producer thread; returns
        the accumulated ``SessionStep`` history (also at ``self.history``).

        An exception raised while iterating ``source`` ends the stream: the
        panes already queued are processed, then ``run`` re-raises it."""
        if key is not None:
            self._root_key = key
        if self._root_key is None:
            raise ValueError("StreamRuntime needs a PRNG key (constructor or run(key=...))")
        self._producer = threading.Thread(
            target=self._pump, args=(source,), name="stream-runtime-pump", daemon=True
        )
        self._producer.start()
        with self._recorder.active():
            try:
                self._consume(wait=True, max_panes=max_panes)
            finally:
                if not self.queue.closed:
                    self.queue.close()  # early stop: unblock + terminate the producer
                self._producer.join()
                self._producer = None
                self.flush()
                self._retire_all()
        if self._producer_error is not None:
            err, self._producer_error = self._producer_error, None
            raise err
        return self._history

    def process(self, max_panes: int | None = None) -> list:
        """Consume panes already queued via :meth:`offer`, without waiting.

        Leaves up to ``max_inflight`` panes un-retired (pipelined); call
        :meth:`drain` for a full barrier.  Returns steps emitted this call.
        """
        before = len(self._history)
        with self._recorder.active():
            self._consume(wait=False, max_panes=max_panes)
        return self._history[before:]

    def _next_pane_index(self) -> int:
        """Session pane index the next dequeued pane will be stepped as."""
        return self.session.pane_index + (self._staged is not None)

    def _consume(self, wait: bool, max_panes: int | None = None) -> None:
        n = 0
        while max_panes is None or n < max_panes:
            if wait:
                timeout = self.config.stage_flush_s if self._staged is not None else None
            else:
                timeout = 0.0
            with spans.span("runtime.queue_get", pane=self._next_pane_index()) as got:
                arrival = self.queue.get(timeout=timeout)
            if arrival is None:
                if not wait or self.queue.closed:
                    break
                # get() timed out with a pane staged and no successor in
                # sight: flush it rather than trade latency for overlap
                spans.count("runtime.timeout_flushes")
                self.flush()
                continue
            staged = self._stage(arrival, got.t1)
            if self._staged is not None:
                # double buffer: dispatch pane k while pane k+1's H2D
                # transfer (issued above) proceeds asynchronously
                self._dispatch(self._staged)
            self._staged = staged
            n += 1
        if not wait:
            self.flush()

    def _stage(self, arrival: _Arrival, t_dequeue: float) -> _Staged:
        """Issue the pane's host→device transfers (async on real backends)."""
        pane = arrival.pane
        with spans.span("runtime.stage", pane=self._next_pane_index()) as sp:
            staged = dataclasses.replace(
                pane,
                lat=jax.device_put(pane.lat),
                lon=jax.device_put(pane.lon),
                value=jax.device_put(pane.value),
                valid=jax.device_put(pane.valid),
                extra={k: jax.device_put(v) for k, v in pane.extra.items()},
            )
        return _Staged(arrival, staged, t_dequeue, sp.t1)

    def _dispatch(self, staged: _Staged) -> None:
        """Feed one staged pane to the session — pure async dispatch."""
        arrival, pane = staged.arrival, staged.pane
        with spans.span("runtime.dispatch", pane=self.session.pane_index) as sp:
            ledger = self.queue.take_drops()
            if ledger:
                pane = self._attach_drops(pane, ledger)
            self._apply_events()
            self._maybe_shed()
            key = jax.random.fold_in(self._root_key, self.session.pane_index)
            step = self.session.step(key, pane)
            # drop the pane's device inputs inside the span: freeing device
            # buffers takes host time that should be put down to this pane
            pane = staged.pane = None
            self._n_tuples += arrival.size
            self._queue_events(step)
            self._history.append(step)
            markers = self._markers(step)
        if self._t_first is None:
            self._t_first = sp.t0
        self._inflight.append(
            _InFlight(
                pane_index=step.pane_index,
                arrival=arrival,
                t_dequeue=staged.t_dequeue,
                t_staged=staged.t_staged,
                t_dispatch=sp.t0,
                t_dispatched=sp.t1,
                markers=markers,
            )
        )
        while len(self._inflight) > self.config.max_inflight:
            self._retire(self._inflight.popleft())

    def flush(self) -> None:
        """Dispatch the currently staged pane, if any."""
        if self._staged is not None:
            staged, self._staged = self._staged, None
            with self._recorder.active():
                self._dispatch(staged)

    def _markers(self, step) -> object:
        """Device values that complete exactly when this pane's work does:
        every registration's freshest ring state plus any emitted results."""
        rings = [reg.ring[-1].stats for reg in self.session.registrations if reg.ring]
        emitted = [r.estimates for r in step.results.values()]
        return (rings, emitted)

    @staticmethod
    def _attach_drops(pane: WindowBatch, ledger: DropLedger) -> WindowBatch:
        """Fold queue-side drops into the pane's accounting fields so they
        ride the existing chain (pane -> ring -> QueryResult -> session)."""
        causes = dict(getattr(pane, "drop_causes", {}) or {})
        for cause, n in ledger.tuples.items():
            causes[cause] = causes.get(cause, 0) + n
        return dataclasses.replace(
            pane,
            n_dropped=int(getattr(pane, "n_dropped", 0)) + ledger.total_tuples,
            drop_causes=causes,
        )

    # -- retirement: the one blocking boundary, outside the pane loop --------

    def _retire(self, entry: _InFlight) -> None:
        """Wait for a pane ``max_inflight`` dispatches old and record its
        timing.  This is the runtime's only ``block_until_ready`` — it
        bounds device memory in flight and timestamps completion, and by
        construction the pane is (nearly) always already done."""
        with spans.span("runtime.retire", pane=entry.pane_index) as sp:
            jax.block_until_ready(entry.markers)
            entry.markers = None  # freeing the pane's buffers costs ms: count it here
        t = self._t_last = sp.t1
        self._timings.append(
            PaneTiming(
                pane_index=entry.pane_index,
                ingest_s=entry.arrival.ingest_s,
                queue_wait_s=entry.t_dequeue - entry.arrival.t_enqueue,
                stage_s=entry.t_staged - entry.t_dequeue,
                dispatch_s=entry.t_dispatched - entry.t_dispatch,
                latency_s=t - entry.arrival.t_enqueue,
                t_dispatch=entry.t_dispatch,
                t_retired=t,
            )
        )

    def _retire_all(self) -> None:
        while self._inflight:
            self._retire(self._inflight.popleft())

    # -- drain / checkpoint --------------------------------------------------

    def drain(self) -> list:
        """Process everything queued *now*, flush the staged pane, and
        retire all in-flight work (a full pipeline barrier).  Bounded: panes
        admitted after entry are left for the next call."""
        with self._recorder.active():
            budget = self.queue.depth + (1 if self._staged is not None else 0)
            steps = self.process(max_panes=budget) if budget else []
            self.flush()
            self._retire_all()
        return steps

    def checkpoint(self, path=None, keep_last: int | None = None) -> dict:
        """Drain-then-snapshot: queued and staged panes are fully processed
        before the session snapshot is taken, so restoring it and replaying
        the *remaining* source panes (fold_in key discipline) is
        bit-identical to a run that never stopped."""
        self.drain()
        return self.session.checkpoint(path, keep_last=keep_last)

    # -- observability -------------------------------------------------------

    @property
    def history(self) -> list:
        return self._history

    def stats(self) -> RuntimeStats:
        timings = self._timings
        wall = (
            (self._t_last - self._t_first)
            if self._t_first is not None and self._t_last is not None
            else 0.0
        )
        return RuntimeStats(
            panes_processed=len(self._history),
            panes_enqueued=self.queue.total_put,
            tuples_processed=self._n_tuples,
            queue_depth_high_water=self.queue.high_water,
            dropped_tuples_by_cause=dict(self.queue.ledger.tuples),
            dropped_panes_by_cause=dict(self.queue.ledger.panes),
            shed_panes=self.shed_panes,
            overlap_efficiency=_overlap_efficiency(timings),
            wall_s=wall,
            ingest=_percentiles([t.ingest_s for t in timings]),
            queue_wait=_percentiles([t.queue_wait_s for t in timings]),
            stage=_percentiles([t.stage_s for t in timings]),
            dispatch=_percentiles([t.dispatch_s for t in timings]),
            pane_latency=_percentiles([t.latency_s for t in timings]),
            compile_cache=(
                pipe.cache_snapshot()
                if (pipe := getattr(self.session, "pipe", None)) is not None
                and hasattr(pipe, "cache_snapshot")
                else {}
            ),
            uplink={
                "total_comm_bytes": int(
                    getattr(self.session, "total_comm_bytes", 0)
                ),
                "uplink_codec": (
                    spec.fingerprint()
                    if (
                        spec := getattr(
                            getattr(self.session, "pipe", None),
                            "codec_spec",
                            None,
                        )
                    )
                    is not None
                    else None
                ),
            },
            spans=self._recorder.spans(),
            counters=self._recorder.counters(),
        )
