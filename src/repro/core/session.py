"""Continuous-query sessions: registered QuerySets over shared sampling passes.

The paper's system answers *many concurrent* continuous queries over the
same geospatial stream, each with its own SLO.  One-shot ``execute`` calls
re-stratify and re-sample the window once per query; a
:class:`StreamSession` amortizes that work across the whole registered
workload (the StreamApprox / ApproxIoT observation that edge-side
approximate analytics wins by sharing one sampling pass):

  * ``register(query, slo=..., window=...)`` any number of declarative
    :class:`~.query.Query` specs, each with an optional pane-based
    :class:`~.windows.WindowSpec` (tumbling / sliding / hopping).
  * Registrations are partitioned **incrementally** into *fusion groups* —
    queries whose plans share a sampling signature
    (:func:`~.query.fusion_key`: method, mode, ROI) and therefore draw
    identical sampling decisions.  ``register`` inserts into (or creates)
    exactly one group; ``unregister`` removes from (or dissolves) one —
    the rest of the partition, its fused plans, and its compiled edge
    programs are untouched, so a register/unregister storm over thousands
    of tenants never replans the world.  Every admission decision lands in
    ``plan_log`` (a :class:`PlanDecision` audit trail).
  * Each ``step(key, pane)`` runs **one** stratify+EdgeSOS pass and one
    collective per fusion group (:func:`~.query.fuse`).  Due windows then
    emit through a **batched finalize**: queries sharing a *finalize
    signature* (:func:`~.query.finalize_signature` — aggregates, grouping,
    confidence, column layout; ROI/method/mode drop out) are stacked on a
    leading axis and carved out of the shared merged ``ColumnStats`` by one
    jitted ``vmap`` dispatch per signature — one compiled program per
    signature, not per query, matching the per-query path (counts and
    extrema exactly; see ``EdgeCloudPipeline.batched_finalize_fn``).
    ``step.results`` materializes per-query views lazily on access, so a
    pane serving thousands of registered dashboards pays O(signatures)
    dispatches, and only the results actually read pay slicing.
  * Sliding/hopping windows fall out of the mergeable-accumulator design:
    the edge reduces each *pane* (stride-sized sub-window) to per-stratum
    registry pytrees (``{column: {kind: state}}`` — moments, extrema,
    quantile sketches, any registered accumulator); the session keeps a
    ring of panes per query and merges them cloud-side
    (:func:`~.estimators.merge_accs_panes`, one vectorized pass per kind)
    into each window's answer without re-touching raw tuples.
  * Per-query QoS runs through a vectorized feedback controller: the whole
    tenant population's ``(fraction, re_ema, steps)`` mirrors stack into
    ``(Q,)`` arrays (:func:`~.feedback.stack_states`), batched relative
    errors scatter in per signature batch
    (:func:`~.feedback.scatter_observations`), and one
    :func:`~.feedback.update_vector` call advances every controller.
  * **Per-query fraction refinement**: when a preagg fusion group's member
    fractions diverge (or a Bernoulli group's ROIs differ), the group runs
    the *refined* edge program (:func:`~.pipeline._fused_edge_program`):
    one shared stratify + randomness draw, thinned per member to its own
    fraction by nested Horvitz-Thompson subsampling (shared SRS ranks /
    shared Bernoulli uniforms, deterministic in the step key).  Each
    member's estimates, error bounds, and downstream volume then reflect
    its *own* effective fraction — a 10%-fraction query fused with an 80%
    one pays 10% downstream — instead of free-riding the group max.
  * **Checkpoint/restore**: ``checkpoint()`` snapshots every registration's
    pane ring, controller slice, and the session drop/uplink counters to a
    versioned pytree (:mod:`.checkpoint`); ``restore()`` into a freshly
    registered session resumes mid-window bit-identically.
  * ``emit_all(key)`` is the pull-based serving read: finalize every
    registration's *current* window on demand (batched, no pane advance) —
    the path a fleet of polling dashboards hits between panes.

Compiled-program caches live on the :class:`~.pipeline.EdgeCloudPipeline`
(passes keyed by plan value, finalizes keyed by finalize signature), so
churning tenants that re-register structurally-seen queries recompile
nothing; the pipeline's ``cache_stats`` counters make that a testable,
benchmark-gated contract.

Correctness contract (property-tested): with every query at the same
fraction, a session step's estimates are elementwise-identical (same PRNG
key) to running each query through ``pipeline.execute`` independently, in
both ``preagg`` and ``raw`` modes — fusion changes the *cost*, never the
answer; batching finalize across a signature changes the *dispatch count*,
never the answer.  With divergent per-query fractions, refined preagg
members are *still* elementwise-identical to independent ``execute`` at
their own fraction (the nested subsample IS the sample their independent
draw would produce); raw-mode groups keep the group-max behavior, so their
per-query error is never worse than requested.  And the incremental
planner is equivalent to full replanning: after any register/unregister
sequence the group partition, fused plans, and subsequent estimates match
a fresh session registering the survivors in order.

``EdgeCloudPipeline.run_stream`` is a thin shim over a single-query session.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import codec as wirecodec
from . import feedback
from . import query as aqp
from . import spans
from .feedback import SLO, ControllerState
from .query import (
    FusedPlan,
    Plan,
    Query,
    QueryResult,
    finalize_signature,
    fuse,
    fusion_key,
)
from .windows import WindowSpec


class _Pane(NamedTuple):
    """One pane's contribution to a registered query's window ring.

    ``n_sampled`` is this *member's* realized sample of the pane — the
    refined (nested-subsampled / ROI-masked) size when the group ran the
    refined pass, the shared group sample otherwise.
    """

    stats: dict  # column -> {kind: state} registry pytree (query's columns)
    n_sampled: jnp.ndarray
    n_valid: jnp.ndarray
    n_overflow: jnp.ndarray
    n_truncated: jnp.ndarray
    n_dropped: int
    comm_bytes: int


@dataclasses.dataclass
class Registration:
    """Handle for one registered continuous query (returned by ``register``).

    Carries the query's lowered plan, pane ring, and its slice of the
    session's controller state (``fraction``/``re_ema``/``steps``).
    ``slo=None`` means no QoS: the fraction stays fixed.
    """

    qid: int
    query: Query
    slo: SLO | None
    window: WindowSpec
    plan: Plan
    qos_key: str | None  # agg key driving QoS; None holds the fraction
    fraction: float
    re_ema: float = 0.0
    steps: int = 0
    panes_seen: int = 0
    ring: list = dataclasses.field(default_factory=list)
    # running count of tuples *this* query's samples kept (device-lazy);
    # under refinement a low-fraction member accumulates its own, smaller,
    # nested sample here instead of the group max's
    downstream_tuples: int | jnp.ndarray = 0
    # uplink bytes shipped for this query since its previous window emit
    # (host int, exact past 2^31).  Emitted windows report *this* — bytes
    # newly shipped — rather than re-summing every overlapped pane, so
    # sliding/hopping window comm totals over a span add up to the
    # session's actual uplink instead of multiply-counting shared panes.
    pending_comm: int = 0

    @property
    def qos_active(self) -> bool:
        return self.slo is not None and self.qos_key is not None

    @property
    def downstream_bytes(self) -> int:
        """Downstream volume this query's samples cost so far: realized
        kept tuples x the plan's per-tuple layout (see
        :func:`~.query.downstream_tuple_bytes`).  Reading this syncs the
        device-lazy tuple counter."""
        return int(self.downstream_tuples) * aqp.downstream_tuple_bytes(self.plan)


class PlanDecision(NamedTuple):
    """One entry of the session's admission/planning audit trail.

    ``outcome`` is what the incremental planner did to the partition:
    ``new-group`` (first member of a fresh fusion signature), ``joined``
    (inserted into an existing group), ``left`` (removed, group survives),
    or ``dissolved`` (last member removed, group deleted).  ``group_size``
    is the member count *after* the decision.
    """

    seq: int
    action: str  # "register" | "unregister"
    qid: int
    group_key: tuple  # fusion_key of the affected group
    outcome: str  # "new-group" | "joined" | "left" | "dissolved"
    group_size: int


class _FusionGroup:
    """One fusion-signature partition cell, maintained incrementally.

    Owns its member list (registration order), the lazily re-fused carrier
    plan, and memoized references to the pipeline's compiled edge programs
    — so the per-pane hot loop never re-hashes O(members) plan tuples to
    look them up, and a membership change invalidates exactly this group.
    """

    __slots__ = ("key", "members", "_fused", "_pass_fn", "_refined_fn", "_codec")

    def __init__(self, key: tuple):
        self.key = key
        self.members: list[Registration] = []
        self._fused: FusedPlan | None = None
        self._pass_fn = None
        self._refined_fn = None
        # per-stream uplink codec instances ("shared" / member qid -> codec);
        # membership changes drop them so stateful (delta) streams re-open
        # with a keyframe instead of diffing against a differently-shaped
        # previous frame
        self._codec: dict = {}

    def invalidate(self) -> None:
        self._fused = None
        self._pass_fn = None
        self._refined_fn = None
        self._codec = {}

    def fused_plan(self) -> FusedPlan:
        if self._fused is None:
            self._fused = fuse([r.plan for r in self.members])
        return self._fused


class _EmitBatch(NamedTuple):
    """One batched finalize dispatch: ``regs`` queries sharing a finalize
    signature and pane count, their stacked estimates/stats (leading axis
    ``>= len(regs)``, padded rows repeat row 0), and per-member window
    counters ``(n_sampled, n_valid, n_overflow, n_truncated, comm_bytes,
    n_dropped)``."""

    regs: tuple
    estimates: dict  # agg key -> AggEstimate with batch-leading leaves
    stats: dict  # column -> {kind: state} with batch-leading leaves
    counters: tuple


_PENDING = object()


def _carve_result(batch: _EmitBatch, i: int) -> QueryResult:
    """Materialize member ``i``'s :class:`QueryResult` view of a batch."""
    estimates = {
        k: aqp.AggEstimate(*(x[i] for x in est))
        for k, est in batch.estimates.items()
    }
    stats = jax.tree.map(lambda x: x[i], batch.stats)
    n_s, n_v, n_o, n_t, comm, dropped = batch.counters[i]
    return QueryResult(
        estimates=estimates,
        stats=stats,
        n_sampled=n_s,
        n_valid=n_v,
        n_overflow=n_o,
        n_truncated=n_t,
        # host int, never a jnp.int32: cumulative uplink past 2^31 bytes
        # must not wrap negative on long streams
        comm_bytes=comm,
        n_dropped=dropped,
    )


class _LazyResults(dict):
    """``qid -> QueryResult`` mapping over batched finalize output.

    Batch members materialize (slice their rows out of the stacked
    estimates/stats) only on access — iteration, ``values()``, ``items()``,
    ``get`` and ``[]`` all materialize; membership/len/``keys()`` never do.
    A pane that served thousands of registrations therefore pays per-query
    slicing only for the results something actually reads.
    """

    def __init__(self):
        super().__init__()
        self._sources: dict[int, tuple[_EmitBatch, int]] = {}
        self._batches: list[_EmitBatch] = []

    def _add(self, qid: int, batch: _EmitBatch, row: int) -> None:
        dict.__setitem__(self, qid, _PENDING)
        self._sources[qid] = (batch, row)

    def __getitem__(self, qid):
        v = dict.__getitem__(self, qid)
        if v is _PENDING:
            batch, row = self._sources.pop(qid)
            v = _carve_result(batch, row)
            dict.__setitem__(self, qid, v)
        return v

    def get(self, qid, default=None):
        return self[qid] if qid in self else default

    def values(self):  # noqa: D102 - dict interface
        return [self[q] for q in self]

    def items(self):  # noqa: D102 - dict interface
        return [(q, self[q]) for q in self]


class SessionStep(NamedTuple):
    """Outcome of feeding one pane to the session.

    results: qid -> QueryResult for queries whose window emitted this pane
      (a query with stride s emits every s panes; others are absent).
      Batched-finalize members materialize lazily on access
      (:class:`_LazyResults`).
    fractions: qid -> post-update controller fraction, for every
      registration.
    comm_bytes: total edge->cloud payload of this pane's shared passes (one
      per fusion group — the fused uplink cost of the whole QuerySet).
      The analytic dense model by default; the *measured* encoded frame
      bytes when ``PipelineConfig.uplink_codec`` is set.  Always a host
      int — cumulative totals stay exact past 2^31.
    n_dropped: tuples shed before this pane reached the device (bounded
      time windows, ingest-queue backpressure, load shedding).
    pane_index: 0-based index of the pane within the session.
    drop_causes: cause -> tuple-count breakdown of ``n_dropped`` (causes:
      ``late`` / ``queue_full`` / ``shed``; uncaused legacy counts land in
      ``late``).  Do not mutate — it may be the class-level default.
    """

    results: dict
    fractions: dict
    comm_bytes: int
    n_dropped: int
    pane_index: int
    drop_causes: dict = {}


class StreamSession:
    """Continuous-query engine over an :class:`~.pipeline.EdgeCloudPipeline`.

    Typical use::

        sess = StreamSession(pipe)
        speed = sess.register(Query(aggs=(AggSpec("mean", "value"),)),
                              slo=SLO(target_relative_error=0.05))
        occ = sess.register(Query(aggs=(AggSpec("mean", "occupancy"),)),
                            window=WindowSpec("sliding", size=4))
        for step in sess.run(pane_windows(stream, pane_tuples=20_000), key=key):
            if speed.qid in step.results:
                ...  # step.results[speed.qid].estimates["mean_value"]

    All registered queries that share a sampling signature are served by one
    stratify+EdgeSOS pass and one collective per pane; all due queries that
    share a finalize signature emit through one vmapped finalize dispatch
    (``batched_finalize=False`` falls back to the per-query emit loop —
    the A/B ``benchmarks/multitenant_bench.py`` gates).
    """

    def __init__(
        self,
        pipeline,
        *,
        sharded: bool = False,
        initial_fraction: float = 0.8,
        batched_finalize: bool = True,
    ):
        self.pipe = pipeline
        self.sharded = sharded
        self.initial_fraction = float(initial_fraction)
        self.batched_finalize = bool(batched_finalize)
        self.pane_index = 0
        self.total_comm_bytes = 0
        self.total_dropped = 0
        self.total_dropped_by_cause: dict = {}
        self.total_passes = 0  # edge passes run (one per fusion group per pane)
        self._regs: dict[int, Registration] = {}
        self._next_qid = 0
        # incremental fusion partition: fusion_key -> group, insertion order
        self._fusion_groups: dict[tuple, _FusionGroup] = {}
        self._reg_group: dict[int, _FusionGroup] = {}
        self.plan_log: list[PlanDecision] = []
        # jitted emit paths cache on the *pipeline* (like _passes): plan and
        # table both derive from the pipe, so a fresh session over a warmed
        # pipe pays zero first-pane compiles — the contract
        # benchmarks/ingest_throughput.py's warm-up relies on
        self._finalizers = pipeline._finalizers
        # controller layout (qid -> row, stacked SLOs) memo; dirtied by
        # membership changes, rebuilt lazily at the next controller update
        self._rows: dict[int, int] = {}
        self._slo_stack: feedback.StackedSLO | None = None
        self._ctrl_dirty = True

    # -- registration --------------------------------------------------------

    def register(
        self,
        query: Query,
        *,
        slo: SLO | None = None,
        window: WindowSpec | None = None,
        initial_fraction: float | None = None,
    ) -> Registration:
        """Register a continuous query; returns its handle.

        ``slo=None`` disables QoS for this query (fixed fraction).  The
        query joins the session's fusion groups from the next ``step``.
        Admission is incremental: only the one fusion group whose sampling
        signature the plan carries is (lazily) re-fused; every other
        group's fused plan and compiled programs are untouched.
        """
        window = window or WindowSpec()
        plan = self.pipe.plan(query)
        # the first *error-bounded* aggregate drives QoS: sum/mean (eq 5-10
        # CIs) and, since the bounds subsystem, var and p<q> quantiles —
        # but only while their bootstrap is enabled (replicates > 0;
        # disabled bounds report zero-width RE 0, which would collapse the
        # fraction).  min/max report only conservative one-sided
        # order-statistic bounds and count is exact — neither drives the
        # controller.
        boot_on = query.bootstrap_replicates > 0

        def _drives(a) -> bool:
            if a.kind in ("sum", "mean"):
                return True
            return boot_on and (
                a.kind == "var" or aqp.quantile_of(a.kind) is not None
            )

        qos_key = next((a.key for a in query.aggs if _drives(a)), None)
        reg = Registration(
            qid=self._next_qid,
            query=query,
            slo=slo,
            window=window,
            plan=plan,
            qos_key=qos_key,
            fraction=float(initial_fraction if initial_fraction is not None else self.initial_fraction),
        )
        self._next_qid += 1
        self._regs[reg.qid] = reg
        gkey = fusion_key(plan)
        grp = self._fusion_groups.get(gkey)
        outcome = "joined" if grp is not None else "new-group"
        if grp is None:
            grp = _FusionGroup(gkey)
            self._fusion_groups[gkey] = grp
        grp.members.append(reg)
        grp.invalidate()
        self._reg_group[reg.qid] = grp
        self._log_decision("register", reg.qid, gkey, outcome, len(grp.members))
        self._ctrl_dirty = True
        return reg

    def unregister(self, reg: Registration) -> None:
        """Drop a registered query (its pane ring is discarded).

        Removal is incremental: the member leaves its one fusion group
        (which dissolves when emptied); no other group replans.
        """
        if self._regs.pop(reg.qid, None) is None:
            return
        grp = self._reg_group.pop(reg.qid)
        grp.members.remove(reg)
        grp.invalidate()
        if not grp.members:
            del self._fusion_groups[grp.key]
            outcome = "dissolved"
        else:
            outcome = "left"
        self._log_decision("unregister", reg.qid, grp.key, outcome, len(grp.members))
        self._ctrl_dirty = True

    def _log_decision(
        self, action: str, qid: int, gkey: tuple, outcome: str, size: int
    ) -> None:
        self.plan_log.append(
            PlanDecision(
                seq=len(self.plan_log),
                action=action,
                qid=qid,
                group_key=gkey,
                outcome=outcome,
                group_size=size,
            )
        )

    @property
    def registrations(self) -> tuple[Registration, ...]:
        return tuple(self._regs.values())

    def controller_state(self, reg: Registration) -> ControllerState:
        """This registration's slice of the vectorized controller state."""
        return ControllerState(
            fraction=jnp.float32(reg.fraction),
            re_ema=jnp.float32(reg.re_ema),
            steps=jnp.int32(reg.steps),
        )

    # -- fusion machinery ----------------------------------------------------

    def _groups(self) -> list[list[Registration]]:
        """The fusion partition as member lists (compatibility view over the
        incremental group structure): registration order within groups,
        group-creation order across them."""
        return [list(g.members) for g in self._fusion_groups.values()]

    def _analytic_comm(self, fused: FusedPlan, n_rows: int) -> int:
        """Per-shard uplink bytes of one shared pass, computed host-side.

        Mirrors ``_edge_program``'s analytic accounting (it is a static
        property of the plan, not of the data) so the hot loop never blocks
        on the device just to read back a constant.
        """
        plan = fused.shared
        if plan.query.mode == "raw":
            cap = self.pipe.config.raw_capacity
            if cap is None:
                shards = 1
                if self.sharded:
                    shape = self.pipe.mesh.shape
                    shards = math.prod(shape[a] for a in self.pipe.axis_names)
                cap = n_rows // shards
            return aqp.raw_bytes(plan, cap)
        return aqp.preagg_bytes(plan, self.pipe.table.num_slots)

    def _codec_ship(self, grp: _FusionGroup, slot, stats) -> tuple[dict, int]:
        """Ship one uplink stream's registry states through the configured
        wire codec (see :mod:`.codec`): returns the *decoded* states the
        cloud tier consolidates plus the frame's measured encoded bytes —
        the byte accounting truth that replaces :meth:`_analytic_comm`'s
        dense model when ``PipelineConfig.uplink_codec`` is set.

        ``slot`` names the stream within the group (``"shared"`` for the
        union pass, the member qid for refined per-member frames); stateful
        codecs (delta) keep per-stream DPCM state here, dropped on any
        membership change (group invalidation) and on ``restore`` so those
        boundaries re-open with a keyframe.  Encoding is the pane loop's
        one deliberate device sync: the uplink serialization boundary
        itself, where states become wire bytes by definition.
        """
        with spans.span("session.codec", pane=self.pane_index):
            stream = grp._codec.get(slot)
            if stream is None:
                stream = grp._codec[slot] = self.pipe.codec_spec.for_stream()
            spans.count("session.host_syncs")
            return wirecodec.roundtrip(stream, stats)

    def _window_host_counters(self, reg: Registration) -> tuple:
        """This query's window-level host ints ``(comm, dropped)``.

        ``comm`` is the bytes *newly shipped* for this query since its
        previous emit (``pending_comm``), not a re-sum of every pane in
        the ring: a sliding window re-reads panes it already paid for, so
        summing the overlap would report more uplink over a span than the
        session actually spent.  Tumbling windows are unchanged (every
        pane is new).  Read non-destructively — ``emit_all``'s serving
        reads must not consume the counter; ``step`` resets it only after
        a scheduled emit."""
        return reg.pending_comm, sum(p.n_dropped for p in reg.ring)

    def _window_ring(self, reg: Registration) -> tuple:
        """The ring as the finalize program takes it: the panes' stats and
        their ``(n_sampled, n_valid, n_overflow, n_truncated)``, unstacked.
        A multi-pane ring is stacked, merged and its counters summed inside
        the program (see ``EdgeCloudPipeline.finalize_fn``), so the host
        dispatches no per-leaf stack or counter add; a one-pane ring passes
        through, preserving bit-compatibility with ``execute``."""
        panes = reg.ring
        if len(panes) == 1:
            (p,) = panes
            return (p.stats,), ((p.n_sampled, p.n_valid, p.n_overflow, p.n_truncated),)
        with spans.span("session.stack", pane=self.pane_index):
            ring = tuple(p.stats for p in panes)
            counts = tuple(
                (p.n_sampled, p.n_valid, p.n_overflow, p.n_truncated) for p in panes
            )
        leaves = jax.tree.leaves(ring)
        spans.count("session.program_stacked_windows")
        spans.count("session.host_stacked_leaves", len(leaves) // len(panes))
        # from shapes and dtypes: reading ``nbytes`` waits on no device work
        spans.count("session.stacked_bytes", sum(x.nbytes for x in leaves))
        return ring, counts

    def _emit(self, reg: Registration, key) -> QueryResult:
        """Finalize this query's window from its pane ring: one program
        assembles the window and finalizes it.

        ``key`` (the step key) seeds the bootstrap error bounds: a
        one-pane window finalizes with the same key as the shared pass, so
        session bounds are bit-identical to an independent ``execute``."""
        fn = self.pipe.finalize_fn(reg.plan, len(reg.ring))
        ring, counts = self._window_ring(reg)
        with spans.span("session.finalize", pane=self.pane_index):
            estimates, stats, (n_sampled, n_valid, n_overflow, n_truncated) = fn(
                ring, counts, key
            )
        spans.count("session.finalize_dispatches")
        comm, dropped = self._window_host_counters(reg)
        return QueryResult(
            estimates=estimates,
            stats=stats,
            n_sampled=n_sampled,
            n_valid=n_valid,
            n_overflow=n_overflow,
            n_truncated=n_truncated,
            # uplink newly spent since this query's previous emit (host
            # int — exact past 2^31; see _window_host_counters)
            comm_bytes=comm,
            # window-level drop accounting: tuples the window's panes shed
            # upstream (survives checkpoint/restore — the ring carries it)
            n_dropped=dropped,
        )

    def _emit_batch(self, regs: list, num_panes: int, key) -> _EmitBatch:
        """One vmapped finalize over a finalize-signature batch: members'
        rings handed over unstacked, member and pane axes stacked *inside*
        the jitted program (padded to the next power of two by repeating
        row 0, so churning batch widths step through O(log Q) compiled
        programs), key broadcast — each row computes its singleton
        finalize."""
        rings, counts = zip(*(self._window_ring(reg) for reg in regs))
        pad = (1 << (len(regs) - 1).bit_length()) - len(regs)
        fn = self.pipe.batched_finalize_fn(regs[0].plan, num_panes, len(regs) + pad)
        with spans.span("session.finalize", pane=self.pane_index):
            estimates, stats, window_counts = fn(
                list(rings) + [rings[0]] * pad, list(counts) + [counts[0]] * pad, key
            )
        spans.count("session.finalize_dispatches")
        counters = tuple(
            (*c, *self._window_host_counters(reg)) for reg, c in zip(regs, window_counts)
        )
        return _EmitBatch(
            regs=tuple(regs), estimates=estimates, stats=stats, counters=counters
        )

    def _emit_due(self, due: list, key, out: _LazyResults):
        """Emit every due registration into ``out``.

        Batches due queries by ``(finalize_signature, ring length)`` and
        emits each multi-member batch through one vmapped dispatch;
        singleton batches (and ``batched_finalize=False`` sessions) take
        the per-query path.  Returns ``(singles, batches)`` for the
        controller update: materialized ``(reg, result)`` pairs and the
        :class:`_EmitBatch` list (whose relative-error vectors feed the
        controller without materializing per-query views).
        """
        singles: list[tuple] = []
        batch_list = out._batches  # the serving read's stacked-output view
        if not self.batched_finalize:
            for reg in due:
                res = self._emit(reg, key)
                out[reg.qid] = res
                singles.append((reg, res))
            return singles, batch_list
        partition: dict[tuple, list] = {}
        for reg in due:
            bkey = (finalize_signature(reg.plan), len(reg.ring))
            partition.setdefault(bkey, []).append(reg)
        computed: dict[tuple, tuple] = {}
        for reg in due:
            bkey = (finalize_signature(reg.plan), len(reg.ring))
            members = partition[bkey]
            if len(members) == 1:
                res = self._emit(reg, key)
                out[reg.qid] = res
                singles.append((reg, res))
                continue
            entry = computed.get(bkey)
            if entry is None:
                batch = self._emit_batch(members, bkey[1], key)
                rows = {m.qid: i for i, m in enumerate(members)}
                entry = computed[bkey] = (batch, rows)
                batch_list.append(batch)
            out._add(reg.qid, entry[0], entry[1][reg.qid])
        return singles, batch_list

    def emit_all(self, key) -> _LazyResults:
        """Finalize every registration's *current* window on demand — the
        pull-based serving read a polling consumer hits between panes.

        Does not advance panes, windows, or controllers; registrations
        with empty rings (never stepped) are absent.  Batched exactly like
        ``step``'s due-window emit, so Q concurrent dashboards cost
        O(finalize signatures) dispatches, not O(Q)."""
        out = _LazyResults()
        due = [r for r in self._regs.values() if r.ring]
        self._emit_due(due, key, out)
        return out

    # -- the continuous loop -------------------------------------------------

    @staticmethod
    def _refines(fused: FusedPlan, fractions: list[float]) -> bool:
        """Host-side choice of edge program for one group this pane.

        The *shared* pass (one union accumulation at the group-max
        fraction, bit-compatible with the pre-refinement session) serves
        single members and uniform-fraction same-ROI groups; the *refined*
        per-member pass serves divergent-fraction preagg groups and
        cross-ROI Bernoulli groups (which the shared pass cannot express).
        Raw-mode groups always share: their compacted uplink buffer is one
        ROI-filtered sample at the group max.  Neyman groups always share
        too — refined thinning would need per-stratum stddev threading to
        preserve the variance-optimal allocation.
        """
        if len(fused.members) < 2:
            return False
        if fused.cross_roi:
            return True
        if fused.mode != "preagg" or fused.shared.query.method == "neyman":
            return False
        return len(set(fractions)) > 1

    def step(self, key, pane) -> SessionStep:
        """Feed one pane through every fusion group and emit due windows.

        Every group's pass uses ``key`` directly (not folded), so a
        single-group session reproduces ``execute(query, key, ...)`` exactly.
        """
        if not self._regs:
            raise ValueError("step() on a session with no registered queries")
        n_dropped = int(getattr(pane, "n_dropped", 0))
        drop_causes = dict(getattr(pane, "drop_causes", None) or {})
        uncaused = n_dropped - sum(drop_causes.values())
        if uncaused > 0:  # legacy producers: window-level sheds count as late
            drop_causes["late"] = drop_causes.get("late", 0) + uncaused
        emitted = _LazyResults()
        due: list[Registration] = []
        comm_total = 0
        for g, grp in enumerate(list(self._fusion_groups.values())):
            members = grp.members
            fused = grp.fused_plan()
            fractions = [r.fraction for r in members]
            refined = self._refines(fused, fractions)
            with spans.span(
                "session.pass",
                pane=self.pane_index,
                group=g,
                program="refined" if refined else "shared",
            ):
                lat, lon, cols, valid = self.pipe._window_arrays(pane, fused.shared)
                if refined:
                    fn = grp._refined_fn
                    if fn is None:
                        fn = grp._refined_fn = self.pipe._refined_pass_fn(
                            fused, self.sharded
                        )
                    outs, _ = fn(
                        key, lat, lon, cols, valid, jnp.asarray(fractions, jnp.float32)
                    )
                    zero = jnp.int32(0)  # refined pass is preagg-only: no buffer
                    if self.pipe.codec_spec is not None:
                        # refined passes ship one encoded frame per member (each
                        # member's thinned states are its own uplink stream)
                        shipped = [
                            self._codec_ship(grp, reg.qid, st)
                            for reg, (st, _ns, _nv, _no) in zip(members, outs)
                        ]
                        comm = sum(nb for _st, nb in shipped)
                        per_member = [
                            (st, ns, nv, no, zero, nb)
                            for (st, nb), (_st, ns, nv, no) in zip(shipped, outs)
                        ]
                    else:
                        comm = aqp.refined_preagg_bytes(fused, self.pipe.table.num_slots)
                        per_member = [
                            (st, ns, nv, no, zero, comm) for st, ns, nv, no in outs
                        ]
                else:
                    fn = grp._pass_fn
                    if fn is None:
                        fn = grp._pass_fn = self.pipe._pass_fn(fused.shared, self.sharded)
                    stats, n_sampled, n_valid, n_overflow, n_truncated, _ = fn(
                        key, lat, lon, cols, valid, jnp.float32(max(fractions))
                    )
                    if self.pipe.codec_spec is not None and fused.mode == "preagg":
                        # one encoded union frame serves the whole group; the
                        # members below carve the *decoded* states, so their
                        # estimates reflect exactly what crossed the wire
                        stats, comm = self._codec_ship(grp, "shared", stats)
                    else:
                        # analytic, host-side: avoid syncing on the device pass
                        comm = self._analytic_comm(fused, lat.shape[0])
                    per_member = []
                    for reg in members:
                        kinds_map = reg.plan.column_kind_map
                        # carve this query's columns *and* accumulator kinds
                        # out of the shared pass's union states
                        carved = {
                            c: {k: stats[c][k] for k in kinds_map[c]}
                            for c in reg.plan.columns
                        }
                        per_member.append(
                            (carved, n_sampled, n_valid, n_overflow, n_truncated, comm)
                        )
                comm_total += comm
                self.total_passes += 1
                for reg, (stats_m, n_s, n_v, n_o, n_t, comm_m) in zip(members, per_member):
                    reg.ring.append(
                        _Pane(
                            stats=stats_m,
                            n_sampled=n_s,
                            n_valid=n_v,
                            n_overflow=n_o,
                            n_truncated=n_t,
                            n_dropped=n_dropped,
                            comm_bytes=comm_m,
                        )
                    )
                    del reg.ring[: -reg.window.size]
                    reg.panes_seen += 1
                    reg.pending_comm += comm_m
                    reg.downstream_tuples = reg.downstream_tuples + n_s
                    if reg.panes_seen % reg.window.stride == 0:
                        due.append(reg)
        with spans.span("session.emit", pane=self.pane_index):
            singles, batches = self._emit_due(due, key, emitted)
            for reg in due:  # emitted windows consumed their newly-shipped bytes
                reg.pending_comm = 0
            self._update_controllers(singles, batches)
        self.pane_index += 1
        self.total_comm_bytes += comm_total
        self.total_dropped += n_dropped
        for cause, n in drop_causes.items():
            self.total_dropped_by_cause[cause] = (
                self.total_dropped_by_cause.get(cause, 0) + n
            )
        return SessionStep(
            results=emitted,
            fractions={r.qid: r.fraction for r in self._regs.values()},
            comm_bytes=comm_total,
            n_dropped=n_dropped,
            pane_index=self.pane_index - 1,
            drop_causes=drop_causes,
        )

    def run(self, panes, key=None) -> list[SessionStep]:
        """Drive the session over an iterator of panes (one key per pane)."""
        key = key if key is not None else jax.random.key(0)  # edgelint: ignore[EDG001] fixed default seed for driverless runs
        history = []
        for pane in panes:
            key, sub = jax.random.split(key)
            history.append(self.step(sub, pane))
        return history

    # -- fault tolerance -----------------------------------------------------

    def checkpoint(self, path=None, keep_last: int | None = None) -> dict:
        """Snapshot the session's resumable state (pane rings, controller
        slices, drop/uplink counters) to a versioned pytree; ``path`` also
        persists it as an ``.npz`` (see :mod:`.checkpoint`).  O(S · columns)
        floats per open pane — cheap enough to take every pane.

        ``keep_last=K`` rotates the K most recent on-disk snapshots
        (``path``, ``path.1``, ...) instead of overwriting in place."""
        from . import checkpoint as ckpt  # sits above session

        snap = ckpt.snapshot(self)
        if path is not None:
            ckpt.save(snap, path, keep_last=keep_last)
        return snap

    def restore(self, snapshot) -> "StreamSession":
        """Load a snapshot (dict or ``.npz`` path) into this session.

        The session must have re-registered the *same* queries in the same
        order (validated against stored fingerprints); rings, fractions,
        EMA state, and drop counters resume exactly where the snapshot was
        taken, so subsequent steps are bit-identical to a session that
        never restarted (given the same per-pane keys)."""
        from . import checkpoint as ckpt

        ckpt.restore(self, snapshot)
        # controller arrays re-stack from the restored host mirrors at the
        # next update; layout (rows / SLO stack) is membership-keyed and
        # membership did not change, but re-deriving it is cheap and safe.
        # (ckpt.restore itself drops stateful uplink codec streams, so the
        # first pane after any restore path ships a keyframe.)
        self._ctrl_dirty = True
        return self

    # -- vectorized QoS ------------------------------------------------------

    def _controller_layout(self) -> tuple[dict, feedback.StackedSLO]:
        """Memoized (qid -> row) map + stacked SLO parameters for the
        current registration set; rebuilt only after membership changes."""
        if self._ctrl_dirty:
            regs = list(self._regs.values())
            self._rows = {r.qid: i for i, r in enumerate(regs)}
            self._slo_stack = feedback.stack_slos([r.slo or SLO() for r in regs])
            self._ctrl_dirty = False
        return self._rows, self._slo_stack

    @staticmethod
    def _observed_re(reg: Registration, res: QueryResult) -> jnp.ndarray:
        """The scalar RE driving this query's controller entry: its first
        error-bounded aggregate (sum/mean/var/quantile); grouped queries
        report the worst group with a finite RE (all-empty or unidentified
        groups -> inf, which holds the fraction)."""
        rel = jnp.asarray(res.estimates[reg.qos_key].relative_error)
        if rel.ndim:
            finite = jnp.isfinite(rel)
            rel = jnp.where(jnp.any(finite), jnp.max(jnp.where(finite, rel, 0.0)), jnp.inf)
        return rel

    @staticmethod
    def _observed_re_batch(qos_key: str, batch: _EmitBatch) -> jnp.ndarray:
        """Vectorized :meth:`_observed_re` over a batch: the per-row RE
        vector (grouped queries reduce their group axis per row)."""
        rel = jnp.asarray(batch.estimates[qos_key].relative_error)
        if rel.ndim > 1:
            finite = jnp.isfinite(rel)
            rel = jnp.where(
                jnp.any(finite, axis=-1),
                jnp.max(jnp.where(finite, rel, 0.0), axis=-1),
                jnp.inf,
            )
        return rel

    def _update_controllers(self, singles: list, batches: list) -> None:
        """One vectorized controller step over all registrations; only
        queries that emitted an error-bounded result this pane advance.

        Batched emissions feed their stacked relative-error vectors in
        directly (one segment per batch, no per-query materialization);
        singleton emissions stack into one extra segment.  The whole
        population then advances through a single
        :func:`~.feedback.update_vector` call.
        """
        rows = None
        segments = []
        active_rows: list[int] = []
        s_rows, s_re, s_nv = [], [], []
        for reg, res in singles:
            if not reg.qos_active:
                continue
            if rows is None:
                rows, slo_stack = self._controller_layout()
            s_rows.append(rows[reg.qid])
            s_re.append(self._observed_re(reg, res).astype(jnp.float32))
            s_nv.append(res.n_valid.astype(jnp.float32))
        if s_rows:
            segments.append((s_rows, jnp.stack(s_re), jnp.stack(s_nv)))
            active_rows.extend(s_rows)
        for batch in batches:
            qos_key = batch.regs[0].qos_key
            act = [i for i, r in enumerate(batch.regs) if r.qos_active]
            if qos_key is None or not act:
                continue
            if rows is None:
                rows, slo_stack = self._controller_layout()
            rel = self._observed_re_batch(qos_key, batch)
            idx = jnp.asarray(act, jnp.int32)
            b_rows = [rows[batch.regs[i].qid] for i in act]
            n_valid = jnp.stack(
                [batch.counters[i][1] for i in act]
            ).astype(jnp.float32)
            segments.append((b_rows, rel[idx].astype(jnp.float32), n_valid))
            active_rows.extend(b_rows)
        if not active_rows:
            return
        with spans.span("session.controllers", pane=self.pane_index):
            regs = list(self._regs.values())
            state = feedback.stack_states(
                (r.fraction, r.re_ema, r.steps) for r in regs
            )
            re_obs, n_obs = feedback.scatter_observations(len(regs), segments)
            active = [False] * len(regs)
            for i in active_rows:
                active[i] = True
            new = feedback.update_vector(state, re_obs, n_obs, slo_stack, jnp.asarray(active))
            frac = jax.device_get(new.fraction)
            ema = jax.device_get(new.re_ema)
            spans.count("session.host_syncs", 2)
            for i, reg in enumerate(regs):
                if active[i]:
                    reg.fraction = float(frac[i])
                    reg.re_ema = float(ema[i])
                    reg.steps += 1
