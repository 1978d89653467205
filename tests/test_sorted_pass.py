"""The stratum-sorted pass of the ``reduce`` scope (``estimators.accumulate_sorted``).

  * per-slot moments and extrema against the ``jax.ops.segment_*``
    formulation (the oracle, kept here and nowhere in the program) and a
    NumPy float64 reference, over Geohash-5/-6 slot counts, pane sizes on
    and off the 128 lane width, empty, one-tuple and fully masked slots,
    overflow padding, values of mixed sign and magnitude, and both sort key
    encodings (one packed word, or two keys where it does not fit);
  * the compiled ``segment`` edge programs of the dashboard's grouped query
    and refined SRS pair hold no ``f32[S+1]`` scatter and no ``f32[N]``
    gather (the sketch's ``f32[(S+1)*513]`` scatter stays);
  * ``reduce.sorted_states`` / ``reduce.scatter_states`` per pane of the
    five dashboard queries equal the counts of their registered plans.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import RuntimeConfig, StreamRuntime, windows
from repro.core import estimators as E
from repro.core.pipeline import reduce_routes
from repro.data.streams import shenzhen_taxi_stream

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from bench import cell  # noqa: E402

EPS32 = float(np.finfo(np.float32).eps)
# centred second moments against float64: within M2_RTOL relative, plus the
# floor of centring in float32 (a mean rounded to float32 misplaces every
# deviation by up to eps32 * max|y|), M2_CENTRING * eps32 * max|y| * sum|y - mean|
M2_RTOL = 1e-5
M2_CENTRING = 4.0

# name -> (S + 1, N, layout)
CASES = {
    "gh5_20k": (232, 20_000, "mixed"),
    "gh5_200k": (232, 200_000, "mixed"),
    "gh5_odd": (232, 20_011, "mixed"),
    "gh6_20k": (6_558, 20_000, "mixed"),
    "gh6_200k": (6_558, 200_000, "mixed"),
    "gh6_singletons_odd": (6_558, 4_099, "singletons"),
    "gh5_fully_masked": (232, 20_000, "masked"),
    "gh6_overflow_padding": (6_558, 20_011, "padding"),
    # slot, kept flag and position need 33 bits here: the two-key sort
    "gh6_two_key_sort": (6_558, 2**18 + 1, "mixed"),
}


def _case(slots: int, n: int, layout: str, seed: int = 0):
    """Slot ids, sample mask and values of one pane, as numpy arrays."""
    rng = np.random.default_rng(seed)
    # a skewed stream over the first ~2/3 of the slots: the rest stay empty
    sidx = np.minimum(rng.zipf(1.3, n) - 1, (2 * slots) // 3).astype(np.int32)
    mask = rng.random(n) < 0.8
    if layout == "singletons":  # every other slot holds exactly one tuple
        k = min(n // 2, slots // 2)
        sidx[:k] = 2 * np.arange(k)
        sidx[k:] = slots - 1 - (np.arange(n - k) % 3)
    if layout == "masked":
        mask[:] = False
    if layout == "padding":  # a padded tail: invalid tuples go to the overflow slot
        tail = n // 3
        sidx[-tail:] = slots - 1
        mask[-tail:] = False
    mag = 10.0 ** rng.integers(-3, 5, n)
    values = (rng.normal(0.0, 1.0, n) * mag + rng.choice([-250.0, 0.0, 4e3], n)).astype(np.float32)
    if layout == "padding":
        values[-tail:] = 3e30  # must not reach any slot: the padding is masked
    return sidx, mask, values


def _segment_oracle(values, sidx, mask, slots, counts):
    """The scatter formulation the sorted pass replaced: two-pass centred
    moments and masked extrema by ``jax.ops.segment_*``."""
    m = mask.astype(jnp.float32)
    n = jax.ops.segment_sum(m, sidx, num_segments=slots)
    wsum = jax.ops.segment_sum(m * values, sidx, num_segments=slots)
    mean = jnp.where(n > 0, wsum / jnp.maximum(n, 1.0), 0.0)
    centred = values - mean[sidx]
    m2 = jax.ops.segment_sum(m * centred * centred, sidx, num_segments=slots)
    lo = jax.ops.segment_min(jnp.where(mask, values, jnp.inf), sidx, num_segments=slots)
    hi = jax.ops.segment_max(jnp.where(mask, values, -jnp.inf), sidx, num_segments=slots)
    moments = E.StratumStats(n=n, total=counts.astype(jnp.float32), wsum=wsum, m2=m2, mean=mean)
    return moments, E.Extrema(min=lo, max=hi)


def _numpy_reference(values, sidx, mask, slots):
    """Per-slot n, N, sum, centred M2, min and max in float64, and the
    tolerances: the sums' ``(n + 2) * eps32 * sum|y|`` and M2's."""
    s, y = sidx[mask], values[mask].astype(np.float64)
    n = np.bincount(s, minlength=slots)
    total = np.bincount(sidx, minlength=slots)
    wsum = np.bincount(s, weights=y, minlength=slots)
    mean = np.where(n > 0, wsum / np.maximum(n, 1), 0.0)
    m2 = np.bincount(s, weights=(y - mean[s]) ** 2, minlength=slots)
    lo = np.full(slots, np.inf)
    hi = np.full(slots, -np.inf)
    np.minimum.at(lo, s, y)
    np.maximum.at(hi, s, y)
    sum_tol = (n + 2) * EPS32 * np.bincount(s, weights=np.abs(y), minlength=slots)
    spread = np.bincount(s, weights=np.abs(y - mean[s]), minlength=slots)
    y_max = np.where(n > 0, np.maximum(np.abs(lo), np.abs(hi)), 0.0)
    m2_tol = M2_RTOL * m2 + M2_CENTRING * EPS32 * y_max * spread
    return n, total, wsum, m2, lo, hi, sum_tol, m2_tol


@pytest.mark.parametrize("case", list(CASES))
def test_sorted_pass_matches_segment_oracle_and_float64(case):
    slots, n, layout = CASES[case]
    sidx, mask, values = _case(slots, n, layout)
    ref_n, ref_total, ref_wsum, ref_m2, ref_lo, ref_hi, sum_tol, m2_tol = _numpy_reference(
        values, sidx, mask, slots
    )
    counts = jnp.asarray(ref_total, jnp.int32)
    args = (jnp.asarray(values), jnp.asarray(sidx), jnp.asarray(mask))

    sorted_pass = jax.jit(
        lambda v, s, m, c: E.accumulate_sorted(((E.MOMENTS, v, m), (E.EXTREMA, v, m)), s, slots, c)
    )
    oracle = jax.jit(lambda v, s, m, c: _segment_oracle(v, s, m, slots, c))
    got_mom, got_ext = jax.device_get(sorted_pass(*args, counts))
    ora_mom, ora_ext = jax.device_get(oracle(*args, counts))

    # the layout does what it says
    assert (ref_total == 0).any() or layout == "masked"
    if layout == "masked":
        assert ref_n.sum() == 0
    if layout == "singletons":
        assert (ref_total == 1).sum() >= 1_000
    if layout == "padding":
        assert ref_total[-1] >= n // 3

    # counts and extrema: exact, and equal to the scatter formulation
    for got, ora in ((got_mom, ora_mom), (got_ext, ora_ext)):
        for field in got._fields:
            if field in ("n", "total", "min", "max"):
                np.testing.assert_array_equal(getattr(got, field), getattr(ora, field), err_msg=field)
    np.testing.assert_array_equal(got_mom.n, ref_n)
    np.testing.assert_array_equal(got_mom.total, ref_total)
    np.testing.assert_array_equal(got_ext.min, ref_lo.astype(np.float32))
    np.testing.assert_array_equal(got_ext.max, ref_hi.astype(np.float32))

    # sums: the configurations' float32 guarantee, slot by slot; the scatter
    # formulation keeps it too
    for name, mom in (("sorted", got_mom), ("segment", ora_mom)):
        err = np.abs(mom.wsum.astype(np.float64) - ref_wsum)
        assert (err <= sum_tol).all(), (name, float(np.max(err / np.maximum(sum_tol, 1e-300))))
    empty = ref_n == 0
    assert (got_mom.wsum[empty] == 0).all() and (got_mom.m2[empty] == 0).all()
    assert (got_mom.mean[empty] == 0).all()
    n32 = np.maximum(ref_n, 1).astype(np.float32)
    np.testing.assert_array_equal(got_mom.mean, np.where(ref_n > 0, got_mom.wsum / n32, 0.0))

    # centred second moments against float64; exactly zero for one-tuple slots
    err = np.abs(got_mom.m2.astype(np.float64) - ref_m2)
    assert (err <= m2_tol).all(), float(np.max(err / np.maximum(m2_tol, 1e-300)))
    assert (got_mom.m2[ref_n == 1] == 0).all()


# -- the compiled edge programs -------------------------------------------------


def _dashboard(precision: int, pane_tuples: int):
    config = json.loads((REPO_ROOT / "bench/configs" / f"taxi_gh{precision}.json").read_text())
    traffic = json.loads((REPO_ROOT / "bench/traffic/dashboard_backlog.json").read_text())
    traffic = {**traffic, "pane_tuples": pane_tuples}
    sess, regs = cell.build_session(config, traffic, jax.devices())
    stream = shenzhen_taxi_stream(num_vehicles=64, chunk_size=pane_tuples, num_chunks=3, seed=0)
    return sess, regs, list(windows.count_windows(stream, pane_tuples))


def _shapes(hlo_line: str, op: str) -> list:
    """The f32 shapes an HLO instruction ``op`` produces on this line, less
    trailing unit dimensions (a gather of N rows reads ``f32[N,1]``)."""
    head = hlo_line.split(f" {op}(", 1)[0].split("=", 1)[-1]
    shapes = []
    for dims in re.findall(r"f32\[([\d,]+)\]", head):
        shape = [int(d) for d in dims.split(",")]
        while len(shape) > 1 and shape[-1] == 1:
            shape.pop()
        shapes.append(tuple(shape))
    return shapes


PANE = 2_003  # tuples: a pane size no per-slot table shares


@pytest.mark.parametrize("program", ["grouped", "pair"])
@pytest.mark.parametrize("precision", [5, 6])
def test_segment_edge_programs_have_no_per_slot_scatter_or_per_tuple_gather(precision, program):
    sess, regs, panes = _dashboard(precision, PANE)
    slots = sess.pipe.table.num_slots
    qid = regs["pair_0.3" if program == "pair" else program].qid
    group = next(g for g in sess._fusion_groups.values() if qid in {r.qid for r in g.members})
    fused = group.fused_plan()
    fractions = [r.fraction for r in group.members]
    refined = sess._refines(fused, fractions)
    assert refined == (program == "pair")
    lat, lon, cols, valid = sess.pipe._window_arrays(panes[0], fused.shared)
    if refined:
        fn = sess.pipe._refined_pass_fn(fused, False)
        frac = jnp.asarray(fractions, jnp.float32)
    else:
        fn = sess.pipe._pass_fn(fused.shared, False)
        frac = jnp.float32(max(fractions))
    hlo = fn.lower(jax.random.key(0), lat, lon, cols, valid, frac).compile().as_text()

    scatters = [s for line in hlo.splitlines() if " scatter(" in line for s in _shapes(line, "scatter")]
    gathers = [s for line in hlo.splitlines() if " gather(" in line for s in _shapes(line, "gather")]
    assert (slots,) not in scatters, scatters
    assert (PANE,) not in gathers, gathers
    # the sketch keeps its scatter, and the pass sorts
    assert (slots * E.SKETCH_NUM_BINS,) in scatters
    assert " sort(" in hlo


# -- the engagement counters ------------------------------------------------------


def test_reduce_counters_per_pane_match_the_plans():
    sess, regs, panes = _dashboard(5, 1_000)
    routes = []
    for grp in sess._fusion_groups.values():
        fused = grp.fused_plan()
        refined = sess._refines(fused, [r.fraction for r in grp.members])
        for plan in fused.members if refined else (fused.shared,):
            routes += [r for _, _, r in reduce_routes(plan, sess.pipe.config.backend)]
    per_pane = (routes.count("sorted"), routes.count("scatter"))
    # grouped: value and occupancy moments + extrema, two sketches; srs: two
    # moments; bernoulli: moments + extrema; the refined pair: two moments, a sketch
    assert per_pane == (10, 3)

    rt = StreamRuntime(sess, key=jax.random.key(0), config=RuntimeConfig(policy="block"))
    rt.run(panes)
    c = rt.stats().counters
    assert c["reduce.sorted_states"] == 10 * len(panes)
    assert c["reduce.scatter_states"] == 3 * len(panes)
