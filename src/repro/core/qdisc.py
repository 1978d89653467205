"""Queue disciplines for the streaming runtime: bounded ingest with
first-class drop accounting.

The paper's edge nodes sit behind bursty producers (§5.2.4): arrival rate
routinely exceeds compute rate, and the system-level question is not *whether*
to drop but *which tuples, counted where*.  :class:`BoundedPaneQueue` is the
single admission point between a :class:`~.runtime.StreamRuntime`'s producer
thread and its pane loop:

  * ``policy="block"``       producer waits for space — lossless, used when
                             bit-identity with the synchronous driver matters
                             (tests, drains, replay);
  * ``policy="drop-newest"`` arriving pane is shed when full (tail drop —
                             favors in-flight work, the paper's Kafka-producer
                             behavior under burst);
  * ``policy="drop-oldest"`` head-of-line pane is evicted to admit the
                             arrival (favors freshness — recency-biased
                             dashboards).

Every shed pane is recorded in a :class:`DropLedger` keyed by *cause*
(``queue_full`` for policy drops, ``shed`` for load-shedding decimation) and
counted in *tuples*, the same unit as ``WindowBatch.n_dropped`` — plus any
upstream drops the evicted pane was itself carrying (``late`` from bounded
time windows), so no loss ever silently vanishes from the accounting chain
``WindowBatch.n_dropped`` -> ``QueryResult.n_dropped`` -> session diagnostics.
The runtime attaches the pending ledger to the next admitted pane.

Everything here is host-side stdlib (deque + condition variable): no RNG, no
clock reads — the queue is deterministic given the put/get interleaving, and
EDG001-clean inside the core import closure.
"""

from __future__ import annotations

import collections
import dataclasses
import threading

QUEUE_POLICIES = ("block", "drop-newest", "drop-oldest")


class QueueClosed(RuntimeError):
    """``put()`` on a closed queue: the consumer has stopped taking panes."""

# canonical drop causes flowing through WindowBatch.drop_causes
CAUSE_LATE = "late"  # bounded-buffer window overflow (windows.time_windows)
CAUSE_QUEUE_FULL = "queue_full"  # backpressure policy drop at the ingest queue
CAUSE_SHED = "shed"  # load-shedding decimation under saturation


@dataclasses.dataclass
class DropLedger:
    """Tuples (and panes) shed, keyed by cause; mergeable and summable."""

    tuples: dict = dataclasses.field(default_factory=dict)
    panes: dict = dataclasses.field(default_factory=dict)

    def add(self, cause: str, n_tuples: int, n_panes: int = 1) -> None:
        self.tuples[cause] = self.tuples.get(cause, 0) + int(n_tuples)
        self.panes[cause] = self.panes.get(cause, 0) + int(n_panes)

    def merge_causes(self, causes: dict) -> None:
        """Fold an upstream ``WindowBatch.drop_causes`` dict into the ledger
        (tuple counts only — those drops never formed panes here)."""
        for cause, n in (causes or {}).items():
            self.tuples[cause] = self.tuples.get(cause, 0) + int(n)

    @property
    def total_tuples(self) -> int:
        return sum(self.tuples.values())

    def __bool__(self) -> bool:
        return bool(self.tuples or self.panes)


def _pane_tuples(pane) -> int:
    """Valid-tuple count of a pane, host-side (numpy mask sum)."""
    size = getattr(pane, "size", None)
    return int(size) if size is not None else 0


class BoundedPaneQueue:
    """Thread-safe bounded FIFO of panes with drop-accounted admission.

    ``put`` is called from the producer thread, ``get`` from the runtime's
    pane loop.  Shedding (both policy drops and decimation) happens at
    admission so a saturated queue costs the producer O(1) — the paper's
    design point that backpressure must be cheaper than the work it sheds.
    """

    def __init__(self, capacity: int = 8, policy: str = "drop-newest"):
        if capacity < 1:
            raise ValueError(f"queue capacity must be >= 1; got {capacity}")
        if policy not in QUEUE_POLICIES:
            raise ValueError(f"policy must be one of {QUEUE_POLICIES}; got {policy!r}")
        self.capacity = int(capacity)
        self.policy = policy
        self._items: collections.deque = collections.deque()
        self._cond = threading.Condition()
        self._closed = False
        self._pending = DropLedger()  # drops awaiting attachment to a pane
        self._decimate = 0  # shed mode: admit 1 of every k arrivals (0 = off)
        self._arrivals = 0
        self.high_water = 0  # max depth ever observed
        self.total_put = 0  # panes admitted
        self.ledger = DropLedger()  # lifetime drops (monotonic; for stats)

    # -- producer side -------------------------------------------------------

    def put(self, pane, timeout: float | None = None) -> bool:
        """Offer a pane; returns True iff *this* pane was admitted.

        Raises :class:`QueueClosed` once :meth:`close` has been called.
        Under ``drop-oldest`` the arrival is admitted by evicting the head;
        under ``drop-newest`` a full queue sheds the arrival; under
        ``block`` the call waits for space (or ``timeout``).  Decimation
        (see :meth:`set_decimation`) sheds ahead of the policy check.
        """
        with self._cond:
            if self._closed:
                raise QueueClosed("put() on a closed BoundedPaneQueue")
            self._arrivals += 1
            if self._decimate > 1 and (self._arrivals - 1) % self._decimate:
                self._drop(pane, CAUSE_SHED)
                return False
            if len(self._items) >= self.capacity:
                if self.policy == "drop-newest":
                    self._drop(pane, CAUSE_QUEUE_FULL)
                    return False
                if self.policy == "drop-oldest":
                    self._drop(self._items.popleft(), CAUSE_QUEUE_FULL)
                else:  # block
                    ok = self._cond.wait_for(
                        lambda: len(self._items) < self.capacity or self._closed,
                        timeout=timeout,
                    )
                    if self._closed:
                        raise QueueClosed("put() on a closed BoundedPaneQueue")
                    if not ok:
                        self._drop(pane, CAUSE_QUEUE_FULL)
                        return False
            self._items.append(pane)
            self.total_put += 1
            self.high_water = max(self.high_water, len(self._items))
            self._cond.notify_all()
            return True

    def _drop(self, pane, cause: str) -> None:
        n = _pane_tuples(pane)
        self._pending.add(cause, n)
        self.ledger.add(cause, n)
        # the shed pane's own upstream drops must not vanish with it
        upstream = getattr(pane, "drop_causes", None) or {}
        self._pending.merge_causes(upstream)
        self.ledger.merge_causes(upstream)

    def close(self) -> None:
        """No more puts; pending gets drain the queue then return None."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    # -- consumer side -------------------------------------------------------

    def get(self, timeout: float | None = None):
        """Next pane in FIFO order; None once closed *and* drained (or on
        timeout)."""
        with self._cond:
            self._cond.wait_for(
                lambda: self._items or self._closed, timeout=timeout
            )
            if not self._items:
                return None
            pane = self._items.popleft()
            self._cond.notify_all()
            return pane

    def take_drops(self) -> DropLedger:
        """Drain the pending drop ledger (drops not yet attached to a pane).
        The runtime calls this after each successful ``get`` and folds the
        result into that pane's ``n_dropped``/``drop_causes``."""
        with self._cond:
            out, self._pending = self._pending, DropLedger()
            return out

    # -- control / observability --------------------------------------------

    def set_decimation(self, k: int) -> None:
        """Load-shedding decimation: admit 1 of every ``k`` arrivals
        (``k <= 1`` disables).  Deterministic counter-based thinning — no
        RNG in the core closure."""
        with self._cond:
            self._decimate = int(k)

    @property
    def depth(self) -> int:
        with self._cond:
            return len(self._items)

    @property
    def closed(self) -> bool:
        return self._closed
