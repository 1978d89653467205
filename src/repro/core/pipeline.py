"""EdgeApproxGeo query engine (paper Algorithm 2 + the declarative layer).

The pipeline executes declarative :class:`~.query.Query` specs over stream
windows.  A query is lowered (``query.lower``) into the two halves of the
edge-cloud split:

Edge tier  = the mesh shards along the data axes: each shard independently
             stratifies + EdgeSOS-samples its local window (no cross-shard
             communication in the sampling path) and reduces every column
             the query references to its plan-declared set of mergeable
             per-stratum accumulator states (``{kind: state}`` registry
             pytrees: moments / extrema / quantile sketch / anything
             registered) — the *edge partial-aggregation program*.  The
             moment reductions run on a configurable backend
             (``PipelineConfig.backend``):

               * ``"segment"`` — one stratum-sorted pass
                 (``estimators.accumulate_sorted``): one sort keyed on the
                 stratum, the sample mask and the position carries every
                 column, and one segmented scan yields every column's
                 moment and extrema states without a scatter (the portable
                 path and the parity oracle; the sketch keeps its own
                 reduction);
               * ``"pallas"``  — ONE fused multi-column edge-reduce pass
                 (``kernels/edge_reduce``): all fusion-group columns'
                 moment rows contract against the one-hot stratum tile in
                 a single MXU sweep per window; off-TPU this lowers to the
                 equivalent single-pass stacked segment reduce.
Cloud tier = the post-collective computation: consolidate shard partials
             and finalize each aggregate into an ``AggEstimate`` with error
             bounds, optionally grouped by stratum / neighborhood — the
             *consolidation query*.  The QoS feedback controller closes the
             loop on the reported relative error.

Two transmission modes (paper §3.6.4), chosen per query:
  * 'preagg' — shards reduce to per-stratum accumulators; one psum of the
    moment vectors plus a pmin/pmax of the extrema, O(S · columns) floats,
    crosses the interconnect.  The default and the paper's bandwidth-saving
    mode.
  * 'raw'    — shards compact kept tuples (stratum id + every referenced
    column) into a padded buffer and all-gather it.  Collective bytes scale
    with the kept sample, not with strata.

Both modes produce identical estimates for the same sample, for every
aggregate kind (tested).

Entry points:
  * ``execute(query, key, window, fraction)`` — the one-shot query engine;
    accepts a ``WindowBatch`` (multi-column) or a mapping of arrays.
  * ``session.StreamSession`` — the continuous-query engine: registered
    QuerySets share one sampling pass per pane via plan fusion; its edge
    half is this pipeline's ``_pass_fn`` (the same program as ``execute``
    minus finalize).  ``run_stream`` is a thin shim over a single-query
    session.
  * ``process_window(key, lat, lon, value, valid, fraction)`` — legacy
    single-estimate API, kept as a thin shim over the canonical
    ``SUM/MEAN(value)`` query; bit-compatible with the pre-query pipeline.
"""

from __future__ import annotations

import dataclasses
import operator
from functools import partial, reduce
from typing import Mapping, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from . import codec as wirecodec
from . import estimators, feedback, sampling
from . import query as aqp

from .estimators import Estimate, StratumStats
from .query import AggEstimate, AggSpec, Plan, Query, QueryResult
from .sampling import SampleResult
from .stratify import StratumTable
from .windows import WindowBatch


BACKENDS = ("segment", "pallas", "fused")

STAGING_DTYPES = ("float32", "bfloat16")

# registry kinds the megakernel emits stat rows for in one pass; plans
# referencing any other kind keep the per-kind accumulate path for it
_FUSED_STAT_KINDS = frozenset({"moments", "extrema", "sketch"})


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Deployment-level defaults; per-query settings live on ``Query``.

    ``backend`` selects the edge reduction implementation:

    * ``"segment"`` — per-column segment ops, the portable parity oracle;
    * ``"pallas"`` — fused multi-column edge-reduce (the Pallas MXU kernel
      on TPU, its single-pass stacked-segment equivalent elsewhere);
      sampling co-dispatches geohash encoding and Bernoulli selection
      through their kernels on TPU;
    * ``"fused"`` — the single-traversal edge megakernel
      (``kernels/edge_megakernel``): geohash + stratify + threshold
      sampling + moments/extrema/sketch stat rows in ONE Pallas pass per
      pane — the intermediate ``sidx``/``mask``/one-hot arrays never
      reach HBM (SRS keeps its rank sort outside, stats still fuse).
      Off-TPU it lowers to the equivalent stacked segment program.

    ``staging_dtype`` (fused backend only) is the dtype value columns are
    *staged* in on their way into the kernel — ``"bfloat16"`` halves the
    value-column VMEM/HBM traffic; every kernel accumulator stays f32
    (EDG004's contract), so only the input rounding differs.

    ``uplink_codec`` selects the preagg wire format (:mod:`.codec`):
    ``None`` ships the dense analytic payload; ``"sparse"`` /
    ``"topk<k>"`` / ``"quantize16"`` / ``"quantize8"`` / ``"delta"``
    route every preagg uplink frame through the named codec — estimates
    then consolidate from the *decoded* states and the session/runtime
    byte accounting reports the measured encoded bytes instead of the
    dense model.  Raw-mode queries are untouched (their compacted tuple
    buffer is already sample-proportional).
    """

    method: str = "srs"  # srs | bernoulli | neyman  (legacy-API default)
    mode: str = "preagg"  # preagg | raw              (legacy-API default)
    confidence: float = 0.95
    raw_capacity: int | None = None  # static per-shard buffer for raw mode
    backend: str = "segment"  # segment | pallas | fused (edge reduction)
    staging_dtype: str = "float32"  # float32 | bfloat16 (fused kernel inputs)
    uplink_codec: str | None = None  # None | sparse | topk<k> | quantize{8,16} | delta

    def __post_init__(self):
        wirecodec.resolve_codec(self.uplink_codec)  # fail fast on bad specs
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}; got {self.backend!r}")
        if self.staging_dtype not in STAGING_DTYPES:
            raise ValueError(
                f"staging_dtype must be one of {STAGING_DTYPES}; got {self.staging_dtype!r}"
            )
        if self.staging_dtype != "float32" and self.backend != "fused":
            raise ValueError(
                "staging_dtype is a fused-backend knob: reduced-precision "
                "staging requires backend='fused' (accumulation stays f32 "
                "on every backend)"
            )


class WindowResult(NamedTuple):
    estimate: Estimate
    stats: StratumStats
    n_sampled: jnp.ndarray
    n_valid: jnp.ndarray
    n_overflow: jnp.ndarray  # tuples outside the region of interest
    comm_bytes: jnp.ndarray  # analytic edge->cloud payload size of this mode


# remove the out-of-region slot from estimation (kept in aux only);
# canonical implementation lives with the accumulators in estimators.py
_zero_overflow = estimators.zero_overflow_stats


def edge_sample(
    key,
    table: StratumTable,
    lat: jnp.ndarray,
    lon: jnp.ndarray,
    valid: jnp.ndarray,
    fraction,
    method: str,
    stddev: jnp.ndarray | None = None,
    backend: str = "segment",
) -> tuple[jnp.ndarray, SampleResult]:
    """Edge-local half of Algorithm 2: stratify + EdgeSOS sample."""
    sidx = table.assign(lat, lon, backend=backend)
    sidx = jnp.where(valid, sidx, table.num_strata)  # padding -> overflow
    result = sampling.edgesos(
        key, sidx, table.num_slots, fraction, method=method, stddev=stddev,
        backend=backend,
    )
    mask = result.mask & valid
    weight = jnp.where(valid, result.weight, 0.0)
    # population counts must also exclude padding
    counts = jax.ops.segment_sum(
        valid.astype(jnp.int32), sidx, num_segments=table.num_slots
    )
    n_k = jax.ops.segment_sum(mask.astype(jnp.int32), sidx, num_segments=table.num_slots)
    return sidx, SampleResult(mask=mask, weight=weight, n_k=n_k, counts=counts)


# registry kinds each backend's kernel emits states for; every other
# (column, kind) state is reduced through the registry
_KERNEL_KINDS = {"segment": frozenset(), "pallas": frozenset({"moments"}), "fused": _FUSED_STAT_KINDS}


def reduce_routes(plan: Plan, backend: str) -> tuple:
    """``(column, kind, route)`` for every state a pass reduces for ``plan``:
    ``"kernel"`` (the backend's kernel emits it), ``"sorted"`` (the pass's
    stratum-sorted scan, :func:`~.estimators.accumulate_sorted`) or
    ``"scatter"`` (the kind's own ``accumulate``).  Moments are reduced for
    every column.  Known from the plan at trace time."""
    kinds_map = plan.column_kind_map
    routes = []
    for c in plan.columns:
        for kind in dict.fromkeys(("moments",) + tuple(kinds_map[c])):
            if kind in _KERNEL_KINDS[backend]:
                route = "kernel"
            elif estimators.accumulator(kind).sorted_pass:
                route = "sorted"
            else:
                route = "scatter"
            routes.append((c, kind, route))
    return tuple(routes)


def _accumulate_columns(
    plan: Plan,
    cfg: PipelineConfig,
    cols: Mapping[str, jnp.ndarray],
    sidx,
    mask,
    num_slots: int,
    counts,
) -> dict:
    """Reduce every referenced column to its plan-declared registry states.

    The moment states of ALL columns come from one fused multi-column
    edge-reduce pass when ``cfg.backend == "pallas"`` (the MXU kernel on
    TPU, the stacked single-pass segment reduce elsewhere) — one window
    traversal for the whole fusion group.  The other states follow
    :func:`reduce_routes`: every sorted-pass state (moments and extrema of
    every column on the ``"segment"`` backend) comes from ONE
    stratum-sorted pass, and the other kinds (the sketch) accumulate
    through their own registry entries.
    """
    with jax.named_scope("reduce"):
        kinds_map = plan.column_kind_map
        stats: dict = {c: {} for c in plan.columns}
        if cfg.backend == "pallas":
            from ..kernels.edge_reduce import edge_reduce

            stacked = jnp.stack([cols[c].astype(jnp.float32) for c in plan.columns])
            cnt, s1, s2 = edge_reduce(sidx, stacked, mask, num_slots)
            for i, c in enumerate(plan.columns):
                stats[c]["moments"] = estimators.stats_from_raw_moments(
                    cnt, s1[i], s2[i], counts
                )
        elif cfg.backend == "fused":
            # a given sample's moment/extrema/sketch rows in one megakernel
            # sweep (sidx mode, keep == mask via the zero-score/one-threshold
            # degenerate compare); kinds outside the fused set fall through to
            # the registry loop below
            from ..kernels.edge_megakernel import edge_megakernel

            ext_idx, sk_idx = _kernel_layout(plan.columns, kinds_map)
            res = edge_megakernel(
                _stack_staged(cfg, plan.columns, cols),
                mask.astype(jnp.float32)[None],
                jnp.zeros((1,) + mask.shape, jnp.float32),
                jnp.ones((1, num_slots), jnp.float32),
                num_slots,
                sidx=sidx[None],
                ext_idx=ext_idx,
                sk_idx=sk_idx,
            )
            stats = _stats_from_mega(
                plan.columns, kinds_map, res, 0, res.keep[0], counts,
                plan.columns, ext_idx, sk_idx,
            )
        in_sort = []
        for c, kind, route in reduce_routes(plan, cfg.backend):
            if route == "sorted":
                in_sort.append((c, kind))
            elif route == "scatter":
                stats[c][kind] = estimators.accumulator(kind).accumulate(
                    cols[c], sidx, mask, num_slots, counts=counts
                )
        states = estimators.accumulate_sorted(
            [(estimators.accumulator(k), cols[c], mask) for c, k in in_sort],
            sidx, num_slots, counts,
        )
        for (c, kind), state in zip(in_sort, states):
            stats[c][kind] = state
        return stats


def _plan_fusable(plan: Plan) -> bool:
    """True when every referenced kind has megakernel stat rows — the
    condition for serving the plan from the single-traversal pass (other
    kinds need the materialized ``sidx``/``mask`` the megakernel skips)."""
    kinds_map = plan.column_kind_map
    return all(set(kinds_map[c]) <= _FUSED_STAT_KINDS for c in plan.columns)


def _kernel_layout(columns, kinds_map) -> tuple[tuple, tuple]:
    """Column positions that get extrema / sketch rows in the megakernel."""
    ext_idx = tuple(i for i, c in enumerate(columns) if "extrema" in kinds_map.get(c, ()))
    sk_idx = tuple(i for i, c in enumerate(columns) if "sketch" in kinds_map.get(c, ()))
    return ext_idx, sk_idx


def _stack_staged(cfg: PipelineConfig, columns, cols) -> jnp.ndarray:
    """Stack value columns in the configured staging dtype (fused backend):
    bf16 staging halves the kernel's value-column traffic; accumulation is
    f32 on every path, so only input rounding differs."""
    dt = jnp.bfloat16 if cfg.staging_dtype == "bfloat16" else jnp.float32
    return jnp.stack([cols[c] for c in columns]).astype(dt)


def _stats_from_mega(
    columns, kinds_map, res, m, keep, counts, union_cols, ext_idx, sk_idx
) -> dict:
    """Adopt member ``m``'s megakernel stat rows into registry states.

    ``columns`` is the member's own column list; positions resolve against
    ``union_cols`` (the kernel's value-column layout, a superset for refined
    fused groups).  ``keep`` is the per-slot kept-count row to use as the
    moment count (callers patch latlon-mode overflow residuals in first).
    """
    pos = {c: i for i, c in enumerate(union_cols)}
    e_pos = {i: e for e, i in enumerate(ext_idx)}
    k_pos = {i: k for k, i in enumerate(sk_idx)}
    stats: dict = {}
    for c in columns:
        i = pos[c]
        d = {
            "moments": estimators.MOMENTS.from_kernel_rows(
                keep, res.s1[m, i], res.s2[m, i], counts
            )
        }
        for kind in kinds_map.get(c, ()):
            if kind == "extrema":
                d[kind] = estimators.EXTREMA.from_kernel_rows(
                    res.mins[m, e_pos[i]], res.maxs[m, e_pos[i]]
                )
            elif kind == "sketch":
                d[kind] = estimators.SKETCH.from_kernel_rows(res.bins[m, k_pos[i]])
        stats[c] = d
    return stats


def _edge_program(
    plan: Plan,
    table: StratumTable,
    cfg: PipelineConfig,
    key,
    lat,
    lon,
    cols: Mapping[str, jnp.ndarray],
    valid,
    fraction,
    axes=None,
):
    """The lowered edge half of a plan (+ the consolidating collective).

    Returns ``(stats, n_sampled, n_valid, n_overflow, n_truncated,
    comm_bytes)`` where ``stats`` maps column -> globally merged
    ``{kind: state}`` accumulator dict.  With ``axes`` set this runs inside
    shard_map and consolidation is a collective; otherwise it is the
    single-edge-node program.
    """
    q = plan.query
    if axes is not None:
        key = jax.random.fold_in(key, jax.lax.axis_index(axes))
    ok = valid & aqp.roi_mask(plan, table, lat, lon)
    if (
        cfg.backend == "fused"
        and q.mode != "raw"
        and _plan_fusable(plan)
        and q.method in ("srs", "bernoulli")
        # latlon-mode overflow residuals need a scalar threshold; a
        # per-stratum Bernoulli fraction falls back to the two-pass path
        and (q.method == "srs" or jnp.ndim(fraction) == 0)
    ):
        stats, n_sampled, n_valid, n_overflow = _fused_member_program(
            plan, table, cfg, key, lat, lon, cols, ok, valid, fraction, axes
        )
        comm = jnp.int32(aqp.preagg_bytes(plan, table.num_slots))
        return stats, n_sampled, n_valid, n_overflow, jnp.int32(0), comm
    sidx, sample = edge_sample(
        key, table, lat, lon, ok, fraction, q.method, backend=cfg.backend
    )
    if q.mode == "raw":
        cap = cfg.raw_capacity or lat.shape[0]
        packed = sampling.compact(
            sample.mask, cap, sidx, *[cols[c] for c in plan.columns]
        )
        # kept tuples beyond the static buffer are silently shed by
        # compact(); account for them so QueryResult can surface the loss
        kept = jnp.sum(sample.mask.astype(jnp.int32))
        n_truncated = jnp.maximum(kept - jnp.int32(min(cap, lat.shape[0])), 0)
        counts = sample.counts
        if axes is not None:
            with jax.named_scope("collective"):
                packed = tuple(jax.lax.all_gather(p, axes, tiled=True) for p in packed)
                counts = jax.lax.psum(counts, axes)
        v_ok, v_sidx = packed[0], packed[1]
        gathered = {c: packed[2 + i] for i, c in enumerate(plan.columns)}
        stats = _accumulate_columns(
            plan, cfg, gathered, v_sidx, v_ok, table.num_slots, counts
        )
        comm = jnp.int32(aqp.raw_bytes(plan, cap))
        n_sampled = jnp.sum(sample.mask.astype(jnp.int32))
        n_valid = jnp.sum(ok.astype(jnp.int32))
        n_overflow = sample.counts[-1] + jnp.sum((valid & ~ok).astype(jnp.int32))
        if axes is not None:
            n_sampled = jax.lax.psum(n_sampled, axes)
            n_valid = jax.lax.psum(n_valid, axes)
            n_overflow = jax.lax.psum(n_overflow, axes)
            n_truncated = jax.lax.psum(n_truncated, axes)
    else:
        stats, n_sampled, n_valid, n_overflow = _member_reduce(
            plan, table, cfg, cols, sidx, sample.mask, ok, valid, sample.counts, axes
        )
        n_truncated = jnp.int32(0)
        comm = jnp.int32(aqp.preagg_bytes(plan, table.num_slots))
    return stats, n_sampled, n_valid, n_overflow, n_truncated, comm


def _member_reduce(
    plan: Plan, table: StratumTable, cfg: PipelineConfig, cols, sidx, mask, ok,
    valid, counts, axes,
):
    """One plan's preagg reduce + consolidate + counters for a given sample.

    The canonical implementation shared by :func:`_edge_program`'s preagg
    branch and the refined fused pass (:func:`_fused_edge_program`): a
    refined member whose mask equals its independent draw gets bit-identical
    states *by construction*, because both paths run this exact program."""
    stats = _accumulate_columns(plan, cfg, cols, sidx, mask, table.num_slots, counts)
    n_sampled = jnp.sum(mask.astype(jnp.int32))
    return _consolidate(plan, stats, n_sampled, ok, valid, counts, axes)


def _consolidate(plan: Plan, stats, n_sampled, ok, valid, counts, axes):
    """Shared tail of every preagg path: the consolidating collective over
    accumulator states plus the sample/validity/overflow counters."""
    with jax.named_scope("collective"):
        if axes is not None:
            merged: dict = {}
            shared = None
            for c in plan.columns:
                merged[c] = estimators.psum_accs(stats[c], axes, shared=shared)
                shared = shared if shared is not None else merged[c]["moments"]
            stats = merged
        n_valid = jnp.sum(ok.astype(jnp.int32))
        n_overflow = counts[-1] + jnp.sum((valid & ~ok).astype(jnp.int32))
        if axes is not None:
            n_sampled = jax.lax.psum(n_sampled, axes)
            n_valid = jax.lax.psum(n_valid, axes)
            n_overflow = jax.lax.psum(n_overflow, axes)
        return stats, n_sampled, n_valid, n_overflow


def _fused_member_program(
    plan: Plan, table: StratumTable, cfg: PipelineConfig, key, lat, lon, cols,
    ok, valid, fraction, axes,
):
    """One plan's preagg reduce as a SINGLE megakernel traversal.

    The megakernel's unified threshold compare reproduces EdgeSOS sampling
    bit-identically while emitting every fused stat row in the same pass:

      * ``bernoulli`` — the same unsplit-key uniforms
        :func:`~.sampling.bernoulli_sample` draws become the scores and the
        scalar fraction the per-slot threshold; membership resolves
        *in-kernel* from lat/lon against the code table (latlon mode), so
        no ``sidx``/``mask`` array ever materializes.  Tuples outside the
        table land in no slot — their stat rows stay zero (the query layer
        zeroes overflow before estimating) and the overflow *counts* are
        reconstructed as residuals against direct sums.
      * ``srs`` — exact ranks need the per-stratum sort, so stratify +
        :func:`~.sampling.srs_ranks` run outside; ranks vs ``n_k`` is the
        in-kernel compare (exact below 2**24) and sidx mode covers every
        slot, overflow included, exactly.
    """
    from ..kernels.edge_megakernel import edge_megakernel

    q = plan.query
    slots = table.num_slots
    kinds_map = plan.column_kind_map
    ext_idx, sk_idx = _kernel_layout(plan.columns, kinds_map)
    vals = _stack_staged(cfg, plan.columns, cols)
    okf = ok.astype(jnp.float32)[None]
    if q.method == "bernoulli":
        u = jax.random.uniform(key, lat.shape)
        thr = jnp.broadcast_to(jnp.asarray(fraction, jnp.float32), (1, slots))
        res = edge_megakernel(
            vals, okf, u[None], thr, slots,
            lat=lat, lon=lon, codes=table.codes, precision=table.precision,
            ext_idx=ext_idx, sk_idx=sk_idx,
        )
        n_sampled = jnp.sum((ok & (u < fraction)).astype(jnp.int32))
        counts = res.pop[0].astype(jnp.int32)
        counts = counts.at[-1].add(jnp.sum(ok.astype(jnp.int32)) - jnp.sum(counts))
        keep = res.keep[0].at[-1].add(
            n_sampled.astype(jnp.float32) - jnp.sum(res.keep[0])
        )
    else:
        sidx = jnp.where(
            ok, table.assign(lat, lon, backend=cfg.backend), table.num_strata
        )
        ranks, counts_all = sampling.srs_ranks(key, sidx, slots)
        n_k = sampling.allocate_proportional(counts_all, fraction)
        res = edge_megakernel(
            vals, okf,
            ranks.astype(jnp.float32)[None], n_k.astype(jnp.float32)[None],
            slots, sidx=sidx[None], ext_idx=ext_idx, sk_idx=sk_idx,
        )
        counts = res.pop[0].astype(jnp.int32)
        keep = res.keep[0]
        n_sampled = jnp.sum(keep).astype(jnp.int32)
    stats = _stats_from_mega(
        plan.columns, kinds_map, res, 0, keep, counts,
        plan.columns, ext_idx, sk_idx,
    )
    return _consolidate(plan, stats, n_sampled, ok, valid, counts, axes)


def _fused_edge_program(
    fused: aqp.FusedPlan,
    table: StratumTable,
    cfg: PipelineConfig,
    key,
    lat,
    lon,
    cols: Mapping[str, jnp.ndarray],
    valid,
    fractions,
    axes=None,
):
    """The *refined* fused edge pass: per-member nested samples from ONE
    shared stratify + randomness draw (preagg mode only).

    Where :func:`_edge_program` serves a whole fusion group from a single
    union accumulation at the group-max fraction, this program thins the
    shared sample to each member's **own** fraction — and, for Bernoulli
    groups, applies each member's **own** ROI as an accumulation mask —
    producing one ``{column: {kind: state}}`` pytree per member:

      * ``srs`` groups share the per-stratum random ranks
        (:func:`~.sampling.srs_ranks`): member m keeps
        ``ranks < n_k(fractions[m])``, which is *exactly* the SRS its
        independent ``execute`` would draw for the same key, and a subset
        of the group-max sample (nested Horvitz-Thompson subsampling — the
        estimators and :mod:`.bounds` intervals then reflect the member's
        effective fraction through the realized ``n_k``).  ``neyman`` is
        refused (its variance-optimal allocation needs per-stratum stddev
        threading; silently substituting proportional allocation would
        change the sampling design) — neyman groups stay on the shared
        group-max pass.
      * ``bernoulli`` groups share one per-tuple uniform draw: member m
        keeps ``u < fractions[m]`` within its own ROI.  Uniforms are
        stratum- and fraction-independent, so differing-ROI members fuse
        into this one pass (cross-signature fusion) and every member's
        sample is bit-identical to its independent draw.

    Returns ``(members_out, comm)`` with ``members_out[m] = (stats,
    n_sampled, n_valid, n_overflow)``.
    """
    shared = fused.shared
    q = shared.query
    if q.method not in ("srs", "bernoulli"):
        raise NotImplementedError(
            f"refined fused pass supports srs|bernoulli members, not "
            f"{q.method!r}; neyman allocation needs per-stratum stddev "
            "threading (its group keeps the shared group-max pass)"
        )
    if axes is not None:
        key = jax.random.fold_in(key, jax.lax.axis_index(axes))
    if cfg.backend == "fused" and all(_plan_fusable(p) for p in fused.members):
        return _fused_refined_mega(
            fused, table, cfg, key, lat, lon, cols, valid, fractions, axes
        )
    slots = table.num_slots
    sidx_raw = table.assign(lat, lon, backend=cfg.backend)
    members_out = []
    if q.method == "bernoulli":
        u = jax.random.uniform(key, lat.shape)
        for m, plan_m in enumerate(fused.members):
            ok = valid & aqp.roi_mask(plan_m, table, lat, lon)
            sidx = jnp.where(ok, sidx_raw, table.num_strata)
            mask = (u < fractions[m]) & ok
            counts = jax.ops.segment_sum(
                ok.astype(jnp.int32), sidx, num_segments=slots
            )
            members_out.append(
                _member_reduce(plan_m, table, cfg, cols, sidx, mask, ok, valid, counts, axes)
            )
    else:
        ok = valid & aqp.roi_mask(shared, table, lat, lon)
        sidx = jnp.where(ok, sidx_raw, table.num_strata)
        ranks, counts_all = sampling.srs_ranks(key, sidx, slots)
        counts = jax.ops.segment_sum(ok.astype(jnp.int32), sidx, num_segments=slots)
        for m, plan_m in enumerate(fused.members):
            # allocation over the raw per-slot counts, as edgesos does
            n_k = sampling.allocate_proportional(counts_all, fractions[m])
            mask = (ranks < n_k[sidx]) & ok
            members_out.append(
                _member_reduce(plan_m, table, cfg, cols, sidx, mask, ok, valid, counts, axes)
            )
    comm = jnp.int32(aqp.refined_preagg_bytes(fused, slots))
    return tuple(members_out), comm


def _fused_refined_mega(
    fused: aqp.FusedPlan, table: StratumTable, cfg: PipelineConfig, key,
    lat, lon, cols, valid, fractions, axes,
):
    """The refined fused pass as ONE megakernel traversal for ALL members.

    The kernel's member axis carries the per-member thresholds (Bernoulli:
    each member's fraction; SRS: each member's ``n_k`` allocation) and, for
    Bernoulli groups, each member's own ROI mask — so the window's value
    columns are read once for the whole fusion group instead of once per
    member.  Sampling semantics match :func:`_fused_edge_program`'s segment
    body decision-for-decision (same uniforms / ranks, same threshold
    compare); Bernoulli runs in latlon mode with the overflow-residual
    reconstruction documented on :func:`_fused_member_program`.
    """
    from ..kernels.edge_megakernel import edge_megakernel

    shared = fused.shared
    q = shared.query
    slots = table.num_slots
    members = fused.members
    m_count = len(members)
    fractions = jnp.asarray(fractions, jnp.float32)
    # union value-column layout: every member's stats slice out of one pass
    union_cols: list = []
    union_kinds: dict = {}
    for p in members:
        km = p.column_kind_map
        for c in p.columns:
            if c not in union_kinds:
                union_cols.append(c)
                union_kinds[c] = set()
            union_kinds[c] |= set(km[c])
    ext_idx, sk_idx = _kernel_layout(union_cols, union_kinds)
    vals = _stack_staged(cfg, union_cols, cols)
    members_out = []
    if q.method == "bernoulli":
        u = jax.random.uniform(key, lat.shape)
        ok_m = jnp.stack([valid & aqp.roi_mask(p, table, lat, lon) for p in members])
        scores = jnp.broadcast_to(u[None], (m_count,) + u.shape)
        thr = jnp.broadcast_to(fractions[:, None], (m_count, slots))
        res = edge_megakernel(
            vals, ok_m.astype(jnp.float32), scores, thr, slots,
            lat=lat, lon=lon, codes=table.codes, precision=table.precision,
            ext_idx=ext_idx, sk_idx=sk_idx,
        )
        for m, plan_m in enumerate(members):
            ok = ok_m[m]
            n_sampled = jnp.sum((ok & (u < fractions[m])).astype(jnp.int32))
            counts = res.pop[m].astype(jnp.int32)
            counts = counts.at[-1].add(jnp.sum(ok.astype(jnp.int32)) - jnp.sum(counts))
            keep = res.keep[m].at[-1].add(
                n_sampled.astype(jnp.float32) - jnp.sum(res.keep[m])
            )
            stats = _stats_from_mega(
                plan_m.columns, plan_m.column_kind_map, res, m, keep, counts,
                union_cols, ext_idx, sk_idx,
            )
            members_out.append(
                _consolidate(plan_m, stats, n_sampled, ok, valid, counts, axes)
            )
    else:  # srs: shared ROI + stratify + ranks, per-member n_k thresholds
        ok = valid & aqp.roi_mask(shared, table, lat, lon)
        sidx = jnp.where(
            ok, table.assign(lat, lon, backend=cfg.backend), table.num_strata
        )
        ranks, counts_all = sampling.srs_ranks(key, sidx, slots)
        thr = jnp.stack(
            [
                sampling.allocate_proportional(counts_all, fractions[m]).astype(jnp.float32)
                for m in range(m_count)
            ]
        )
        res = edge_megakernel(
            vals,
            jnp.broadcast_to(ok.astype(jnp.float32)[None], (m_count,) + ok.shape),
            jnp.broadcast_to(ranks.astype(jnp.float32)[None], (m_count,) + ranks.shape),
            thr, slots,
            sidx=jnp.broadcast_to(sidx[None], (m_count,) + sidx.shape),
            ext_idx=ext_idx, sk_idx=sk_idx,
        )
        for m, plan_m in enumerate(members):
            counts = res.pop[m].astype(jnp.int32)
            n_sampled = jnp.sum(res.keep[m]).astype(jnp.int32)
            stats = _stats_from_mega(
                plan_m.columns, plan_m.column_kind_map, res, m, res.keep[m],
                counts, union_cols, ext_idx, sk_idx,
            )
            members_out.append(
                _consolidate(plan_m, stats, n_sampled, ok, valid, counts, axes)
            )
    comm = jnp.int32(aqp.refined_preagg_bytes(fused, slots))
    return tuple(members_out), comm


def _stats_template(plan: Plan) -> dict:
    """Structure-only column -> {kind: state} tree for out_specs."""
    kinds_map = plan.column_kind_map
    return {c: estimators.accs_template(kinds_map[c]) for c in plan.columns}


def _result_template(plan: Plan) -> QueryResult:
    """Structure-only QueryResult (for shard_map out_specs trees)."""
    return QueryResult(
        estimates={a.key: AggEstimate(*(0,) * 7) for a in plan.query.aggs},
        stats=_stats_template(plan),
        n_sampled=0,
        n_valid=0,
        n_overflow=0,
        n_truncated=0,
        comm_bytes=0,
    )


def _window_counts(counts: tuple) -> tuple:
    """A window's ``(n_sampled, n_valid, n_overflow, n_truncated)``: each
    pane's counters summed in ring order (one pane passes through)."""
    return tuple(reduce(operator.add, xs) for xs in zip(*counts))


class EdgeCloudPipeline:
    """Single-program query engine; optionally distributed over mesh axes."""

    def __init__(
        self,
        table: StratumTable,
        config: PipelineConfig = PipelineConfig(),
        mesh=None,
        axis_names: tuple[str, ...] = ("data",),
    ):
        self.table = table
        self.config = config
        self.mesh = mesh
        self.axis_names = axis_names
        # resolved uplink wire codec (None = dense analytic payload);
        # stateful codecs (delta) hand out per-stream instances via
        # for_stream(), so this is the *spec*, never a live DPCM state
        self.codec_spec = wirecodec.resolve_codec(config.uplink_codec)
        self._plans: dict[Query, Plan] = {}
        self._execs: dict[tuple[Query, bool], callable] = {}
        self._passes: dict[tuple[Plan, bool], callable] = {}
        self._refined_passes: dict[tuple, callable] = {}
        # jitted session emit paths, keyed by *finalize signature* (not by
        # query: two queries differing only in ROI/method/mode share one
        # compiled finalize) plus pane count / batch width: sessions share
        # these like _passes, so a fresh session over a warmed pipeline
        # pays no first-pane compile
        self._finalizers: dict[tuple, callable] = {}
        # compiled-program cache accounting, per cache family.  A "miss"
        # is a new trace+compile (or a fresh lowering for "plan"); during
        # steady-state tenant churn every family must hit — the
        # multitenant bench gates the miss delta at zero.
        self.cache_stats: dict[str, dict[str, int]] = {
            f: {"hits": 0, "misses": 0}
            for f in ("plan", "exec", "pass", "refined_pass", "finalize")
        }

    def _cache_event(self, family: str, hit: bool) -> None:
        self.cache_stats[family]["hits" if hit else "misses"] += 1

    @property
    def compile_count(self) -> int:
        """Total compiled-program cache misses across the jitted families
        (``plan`` lowerings are host-side and excluded).  The steady-state
        churn contract: this must not move while tenants register and
        unregister structurally-seen queries."""
        return sum(
            v["misses"] for f, v in self.cache_stats.items() if f != "plan"
        )

    def cache_snapshot(self) -> dict:
        """Copy of the per-family hit/miss counters plus the aggregate
        ``compile_count`` (surfaced through ``RuntimeStats``)."""
        return {
            "families": {f: dict(v) for f, v in self.cache_stats.items()},
            "compile_count": self.compile_count,
        }

    # -- declarative query API ----------------------------------------------

    def plan(self, query: Query) -> Plan:
        """Lower (and cache) a query against this pipeline's stratum table."""
        p = self._plans.get(query)
        self._cache_event("plan", p is not None)
        if p is None:
            p = aqp.lower(query, self.table)
            self._plans[query] = p
        return p

    @property
    def input_sharding(self) -> NamedSharding:
        """Where the sharded programs take a pane's columns: split over the
        data axes, each edge node's contiguous share on its own device."""
        return NamedSharding(self.mesh, P(self.axis_names))

    def _compiled(self, plan: Plan, body, out_template, sharded: bool):
        """Jit ``body(key, lat, lon, cols, valid, fraction, axes=None)`` —
        directly, or wrapped in shard_map over the data axes (shards = edge
        nodes, replicated outputs shaped like ``out_template``).  Either way
        the program is named after ``body`` (``jit_edge_pass`` in the HLO,
        ``PjitFunction(edge_pass)`` on the host trace)."""
        if not sharded:
            return jax.jit(body)
        axes = self.axis_names
        spec = self.input_sharding.spec
        mapped = jax.shard_map(
            partial(body, axes=axes),
            mesh=self.mesh,
            in_specs=(P(), spec, spec, {c: spec for c in plan.columns}, spec, P()),
            out_specs=jax.tree.map(lambda _: P(), out_template),
            check_vma=False,
        )
        return jax.jit(mapped)

    def _query_fn(self, query: Query, sharded: bool):
        fn = self._execs.get((query, sharded))
        self._cache_event("exec", fn is not None)
        if fn is not None:
            return fn
        plan = self.plan(query)
        table, cfg = self.table, self.config

        def execute(key, lat, lon, cols, valid, fraction, axes=None):
            stats, n_sampled, n_valid, n_overflow, n_truncated, comm = _edge_program(
                plan, table, cfg, key, lat, lon, cols, valid, fraction, axes=axes
            )
            return QueryResult(
                # bounds are deterministic in the window key: fused sessions
                # finalize the same stats with the same key bit-identically
                estimates=aqp.finalize(plan, table, stats, key=key),
                stats=stats,
                n_sampled=n_sampled,
                n_valid=n_valid,
                n_overflow=n_overflow,
                n_truncated=n_truncated,
                comm_bytes=comm,
            )

        fn = self._compiled(plan, execute, _result_template(plan), sharded)
        self._execs[(query, sharded)] = fn
        return fn

    def _pass_fn(self, plan: Plan, sharded: bool):
        """Jitted *edge pass* for a lowered plan: stratify + EdgeSOS +
        accumulate + consolidating collective, **without** finalize.

        This is the shared half a :class:`~.session.StreamSession` runs once
        per fusion group and per pane: the returned per-column ``ColumnStats``
        feed any number of per-query finalizes (and pane merges) cloud-side.
        ``execute`` is the degenerate composition pass+finalize in one
        program.
        """
        fn = self._passes.get((plan, sharded))
        self._cache_event("pass", fn is not None)
        if fn is not None:
            return fn
        table, cfg = self.table, self.config

        def edge_pass(key, lat, lon, cols, valid, fraction, axes=None):
            return _edge_program(
                plan, table, cfg, key, lat, lon, cols, valid, fraction, axes=axes
            )

        template = (_stats_template(plan), 0, 0, 0, 0, 0)
        fn = self._compiled(plan, edge_pass, template, sharded)
        self._passes[(plan, sharded)] = fn
        return fn

    def _refined_pass_fn(self, fused: aqp.FusedPlan, sharded: bool):
        """Jitted *refined* fused pass: per-member nested/ROI-masked
        accumulator states from one shared stratify + randomness draw (see
        :func:`_fused_edge_program`).  Takes a ``(M,)`` per-member fraction
        vector in the fraction slot, so controller-driven fraction drift
        never recompiles.
        """
        cache_key = (fused.members, sharded)
        fn = self._refined_passes.get(cache_key)
        self._cache_event("refined_pass", fn is not None)
        if fn is not None:
            return fn
        table, cfg = self.table, self.config

        def refined_pass(key, lat, lon, cols, valid, fractions, axes=None):
            return _fused_edge_program(
                fused, table, cfg, key, lat, lon, cols, valid, fractions, axes=axes
            )

        template = (tuple((_stats_template(p), 0, 0, 0) for p in fused.members), 0)
        fn = self._compiled(fused.shared, refined_pass, template, sharded)
        self._refined_passes[cache_key] = fn
        return fn

    def _finalize_body(self, plan: Plan, num_panes: int):
        """``(ring, key) -> (estimates, merged)`` for one query's window:
        ``ring`` is the window's ``num_panes`` pane stats, unstacked, as the
        session's ring holds them.  The panes are stacked on a leading axis
        and merged here, inside the program, so an emit hands the ring over
        without a per-leaf stack on the host; one pane is a pass-through
        (bit-compatible with ``execute``)."""
        table = self.table

        def finalize(ring, bkey):
            if num_panes == 1:
                (merged,) = ring
            else:
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *ring)
                merged = {
                    c: estimators.merge_accs_panes(stacked[c]) for c in plan.columns
                }
            return aqp.finalize(plan, table, merged, key=bkey), merged

        return finalize

    def finalize_fn(self, plan: Plan, num_panes: int):
        """Jitted cloud-side emit for one registration, cached by *finalize
        signature*: queries that differ only in sampling method / mode /
        ROI share one compiled program (finalize never reads those — see
        :func:`~.query.finalize_signature`).

        ``(ring, counts, key) -> (estimates, merged, window_counts)``:
        ``ring`` is the window's ``num_panes`` pane stats and ``counts`` each
        pane's ``(n_sampled, n_valid, n_overflow, n_truncated)``; the window's
        four counters are their sums, taken in the program too, so an emit
        dispatches this one program and no eager op."""
        key = ("single", aqp.finalize_signature(plan), num_panes)
        fn = self._finalizers.get(key)
        self._cache_event("finalize", fn is not None)
        if fn is not None:
            return fn
        body = self._finalize_body(plan, num_panes)

        def finalize(ring, counts, bkey):
            return (*body(ring, bkey), _window_counts(counts))

        fn = jax.jit(finalize)
        self._finalizers[key] = fn
        return fn

    def batched_finalize_fn(self, plan: Plan, num_panes: int, batch: int):
        """Jitted *vmapped* finalize: one dispatch emits ``batch`` queries
        sharing a finalize signature (key broadcast — each row computes
        what its singleton finalize would: counts and extrema exactly, float
        sums up to the order XLA gives a reduction under ``vmap``, which
        moves the ``var`` bootstrap's bound by an ulp at Geohash-6).  Takes
        the lists of ``batch`` members' rings and pane counts, each as
        :meth:`finalize_fn` takes one; the member axis and the pane axis are
        both stacked inside the compiled program, so the host hands the
        rings over as they are and dispatches nothing per leaf.  Returns
        estimates and merged stats with a leading member axis and each
        member's window counts.  ``batch`` is the padded width (sessions pad
        to the next power of two so tenant churn steps through O(log Q)
        compiled widths, not one per group size)."""
        key = ("batched", aqp.finalize_signature(plan), num_panes, batch)
        fn = self._finalizers.get(key)
        self._cache_event("finalize", fn is not None)
        if fn is not None:
            return fn
        body = jax.vmap(self._finalize_body(plan, num_panes), in_axes=(0, None))

        def finalize_batched(member_rings, member_counts, bkey):
            rings = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *member_rings)
            return (*body(rings, bkey), tuple(_window_counts(c) for c in member_counts))

        fn = jax.jit(finalize_batched)
        self._finalizers[key] = fn
        return fn

    def _window_arrays(self, window, plan: Plan):
        """Host-side: split a WindowBatch / mapping into device inputs."""
        if isinstance(window, WindowBatch):
            cols = window.columns
            lat, lon, valid = window.lat, window.lon, window.valid
        else:
            cols = {k: v for k, v in window.items() if k not in ("lat", "lon", "valid")}
            lat, lon = window["lat"], window["lon"]
            valid = window.get("valid")
        lat = jnp.asarray(lat, jnp.float32)
        lon = jnp.asarray(lon, jnp.float32)
        valid = jnp.ones(lat.shape, bool) if valid is None else jnp.asarray(valid, bool)
        missing = [c for c in plan.columns if c not in cols]
        if missing:
            raise KeyError(f"window has no column(s) {missing}; available: {sorted(cols)}")
        cols = {c: jnp.asarray(cols[c], jnp.float32) for c in plan.columns}
        return lat, lon, cols, valid

    def _codec_rebase(self, plan: Plan, res: QueryResult, key) -> QueryResult:
        """Ship a one-shot query's consolidated states through the uplink
        codec: estimates re-finalize from the *decoded* states (bit-identical
        for lossless codecs — the property tests' contract) and
        ``comm_bytes`` becomes the frame's measured encoded bytes instead of
        the analytic dense model.  One-shot executes open a fresh stream, so
        a delta codec degenerates to a keyframe here."""
        stats, nbytes = wirecodec.roundtrip(self.codec_spec.for_stream(), res.stats)
        counts = ((res.n_sampled, res.n_valid, res.n_overflow, res.n_truncated),)
        estimates, stats, _ = self.finalize_fn(plan, 1)((stats,), counts, key)
        return res._replace(estimates=estimates, stats=stats, comm_bytes=nbytes)

    def execute(self, query: Query, key, window, fraction=1.0) -> QueryResult:
        """Evaluate a declarative query over one window on one edge node.

        ``window`` is a :class:`WindowBatch` or a mapping with ``lat``,
        ``lon``, optional ``valid``, and one array per referenced column.
        """
        plan = self.plan(query)
        lat, lon, cols, valid = self._window_arrays(window, plan)
        fn = self._query_fn(query, sharded=False)
        res = fn(key, lat, lon, cols, valid, jnp.float32(fraction))
        if self.codec_spec is not None and plan.query.mode == "preagg":
            res = self._codec_rebase(plan, res, key)
        # upstream drop accounting is a host-side property of the window
        return res._replace(n_dropped=int(getattr(window, "n_dropped", 0)))

    def execute_sharded(self, query: Query, key, window, fraction=1.0) -> QueryResult:
        """Distributed execute: shards = edge nodes, collective = uplink."""
        if self.mesh is None:
            raise ValueError("pipeline constructed without a mesh")
        plan = self.plan(query)
        lat, lon, cols, valid = self._window_arrays(window, plan)
        fn = self._query_fn(query, sharded=True)
        res = fn(key, lat, lon, cols, valid, jnp.float32(fraction))
        if self.codec_spec is not None and plan.query.mode == "preagg":
            res = self._codec_rebase(plan, res, key)
        return res._replace(n_dropped=int(getattr(window, "n_dropped", 0)))

    # -- legacy single-estimate API (shim over the canonical query) ---------

    def _canonical_query(self, mode: str = "preagg") -> Query:
        """The fixed query the pre-redesign API answered: SUM/MEAN(value)."""
        return Query(
            aggs=(AggSpec("sum", "value"), AggSpec("mean", "value")),
            confidence=self.config.confidence,
            method=self.config.method,
            mode=mode,
        )

    @partial(jax.jit, static_argnums=(0,))
    def process_window(self, key, lat, lon, value, valid, fraction) -> WindowResult:
        plan = self.plan(self._canonical_query())
        stats, n_sampled, n_valid, n_overflow, _trunc, comm = _edge_program(
            plan, self.table, self.config, key, lat, lon, {"value": value}, valid, fraction
        )
        base = stats["value"]["moments"]
        est = estimators.estimate(_zero_overflow(base), self.config.confidence)
        # a moment-only single-column plan ships exactly the legacy payload
        return WindowResult(
            estimate=est,
            stats=base,
            n_sampled=n_sampled,
            n_valid=n_valid,
            n_overflow=n_overflow,
            comm_bytes=comm,
        )

    def process_window_sharded(self, key, lat, lon, value, valid, fraction) -> WindowResult:
        """Legacy distributed API: shim over the canonical query's sharded
        plan (one edge program for both paths), honoring ``config.mode``."""
        if self.mesh is None:
            raise ValueError("pipeline constructed without a mesh")
        fn = self._query_fn(self._canonical_query(mode=self.config.mode), sharded=True)
        res = fn(
            key, lat, lon, {"value": value}, jnp.asarray(valid), jnp.float32(fraction)
        )
        base = res.stats["value"]["moments"]
        est = estimators.estimate(_zero_overflow(base), self.config.confidence)
        # moment-only single-column plans ship the legacy payloads in both
        # modes (preagg 4 vectors, raw 9 bytes/slot), so comm passes through
        return WindowResult(
            estimate=est,
            stats=base,
            n_sampled=res.n_sampled,
            n_valid=res.n_valid,
            n_overflow=res.n_overflow,
            comm_bytes=res.comm_bytes,
        )

    # -- continuous query loop (Algorithm 2) ---------------------------------

    def run_stream(
        self,
        windows,
        slo: feedback.SLO | None = None,
        initial_fraction: float = 0.8,
        key=None,
        sharded: bool = False,
        query: Query | None = None,
    ):
        """Process a stream of WindowBatch under the QoS feedback loop.

        With ``query`` set this is a thin shim over a single-query
        :class:`~.session.StreamSession` (one registered tumbling
        one-pane query): the controller tracks the relative error of the
        query's first *error-bounded* aggregate (sum/mean/var/quantile —
        exact count and one-sided min/max bounds don't drive it).  Grouped queries
        are driven by the worst group with a finite RE (empty groups report
        inf).  A query with no sum/mean aggregate keeps the fraction fixed.
        Register several queries on a session directly to share one
        sampling pass across all of them.
        """
        slo = slo or feedback.SLO()
        key = key if key is not None else jax.random.key(0)  # edgelint: ignore[EDG001] fixed default seed for driverless runs
        if query is not None:
            from .session import StreamSession  # session sits above pipeline

            sess = StreamSession(self, sharded=sharded, initial_fraction=initial_fraction)
            reg = sess.register(query, slo=slo)
            history = []
            for w in windows:
                key, sub = jax.random.split(key)
                step = sess.step(sub, w)
                history.append((step.results[reg.qid], step.fractions[reg.qid]))
            return history, sess.controller_state(reg)
        state = feedback.init_state(initial_fraction)
        history = []
        for w in windows:
            key, sub = jax.random.split(key)
            fn = self.process_window_sharded if sharded else self.process_window
            res = fn(
                sub,
                jnp.asarray(w.lat, jnp.float32),
                jnp.asarray(w.lon, jnp.float32),
                jnp.asarray(w.value, jnp.float32),
                jnp.asarray(w.valid),
                state.fraction,
            )
            state = feedback.update(state, res.estimate.relative_error, res.n_valid, slo)
            # keep the controller fraction device-lazy: a float() here would
            # block every pane on the previous pane's device work
            history.append((res, state.fraction))
        # one host sync at the stream boundary instead of one per pane
        fracs = jax.device_get([f for _, f in history])  # edgelint: ignore[EDG002] single end-of-stream readback
        history = [(res, float(f)) for (res, _), f in zip(history, fracs)]  # edgelint: ignore[EDG002] floats already on host via device_get
        return history, state
