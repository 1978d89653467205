"""Uplink wire-format codec laws (core/codec.py).

Property-tested contracts of the sparse + quantized uplink:

  * lossless codecs (sparse, delta) round-trip every registry kind
    bit-exactly — through direct state round-trips, one-shot execute,
    fused sessions (refined divergent fractions, cross-ROI Bernoulli,
    sliding windows), and the 8-device sharded psum path;
  * lossy codecs keep the moments every bound reads exact: quantize
    never touches ``n``/``total``/sketch bins and reconstructs value rows
    within its declared half-step bound; top-k preserves per-stratum
    sketch masses exactly (HT expansion and quantile inversion stay
    sound);
  * byte accounting is hardened: per-window comm is bytes *newly
    shipped* since the previous emit (sliding == tumbling over a span),
    counters are Python ints that stay exact past 2^31 and survive the
    checkpoint round-trip, and a snapshot taken under one codec refuses
    to restore under another.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.core import (
    SHENZHEN_BBOX,
    AggSpec,
    EdgeCloudPipeline,
    PipelineConfig,
    Query,
    StreamSession,
    WindowSpec,
    checkpoint,
    estimators,
    make_table,
    query as aqp,
    windows,
)
from repro.core import codec as wirecodec
from repro.core.estimators import accumulate_column
from repro.data.streams import shenzhen_taxi_stream

KINDS = ("moments", "extrema", "sketch")
LOSSLESS_SPECS = ("sparse", "delta")
ALL_SPECS = ("sparse", "delta", "topk8", "quantize16", "quantize8")

EXACT_FIELDS = ("value", "moe", "ci_low", "ci_high", "relative_error", "n", "population")

PANE = 6_000


@pytest.fixture(scope="module")
def table():
    return make_table(*SHENZHEN_BBOX, precision=5)


@pytest.fixture(scope="module")
def window():
    stream = shenzhen_taxi_stream(num_chunks=1, seed=0)
    return next(windows.count_windows(stream, PANE))


@pytest.fixture(scope="module")
def panes():
    stream = shenzhen_taxi_stream(num_chunks=2, seed=3)
    return list(windows.count_windows(stream, PANE))[:4]


def _rand_stats(rng, s=64, n=3_000, occupied=5, columns=("value", "occupancy")):
    """A sparse registry tree: data concentrated in ``occupied`` strata."""
    stats = {}
    for c in columns:
        strata = rng.choice(s, size=min(occupied, s), replace=False)
        sidx = jnp.asarray(rng.choice(strata, n), jnp.int32)
        vals = jnp.asarray(rng.normal(40, 12, n), jnp.float32)
        mask = jnp.asarray(rng.random(n) < 0.7)
        stats[c] = accumulate_column(KINDS, vals, sidx, mask, s + 1)
    return stats


def _dense_bytes(stats) -> int:
    return sum(int(np.asarray(x).nbytes) for x in jax.tree.leaves(stats))


def _assert_tree_equal(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=msg)


# -- direct state round-trips --------------------------------------------------


@pytest.mark.parametrize("spec", LOSSLESS_SPECS)
def test_lossless_roundtrip_bit_exact(spec):
    rng = np.random.default_rng(0)
    stats = _rand_stats(rng)
    codec = wirecodec.resolve_codec(spec).for_stream()
    decoded, nbytes = wirecodec.roundtrip(codec, stats)
    _assert_tree_equal(stats, decoded, spec)
    assert 0 < nbytes < _dense_bytes(stats)


@pytest.mark.parametrize("spec", ALL_SPECS)
def test_empty_stats_roundtrip(spec):
    """Zero-occupancy panes cost a few control bytes and decode to the
    identity-filled template bit-exactly (all codecs)."""
    s = 64
    stats = {
        "value": accumulate_column(
            KINDS,
            jnp.zeros((8,), jnp.float32),
            jnp.zeros((8,), jnp.int32),
            jnp.zeros((8,), bool),
            s + 1,
        )
    }
    codec = wirecodec.resolve_codec(spec).for_stream()
    decoded, nbytes = wirecodec.roundtrip(codec, stats)
    _assert_tree_equal(stats, decoded, spec)
    assert nbytes < 128  # preamble + control words only: nothing occupied


@settings(deadline=None, max_examples=10)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    occupied=st.integers(min_value=1, max_value=48),
)
def test_codec_roundtrip_laws_property(seed, occupied):
    """Every codec, arbitrary sparse states: count rows exact, sketch
    masses exact, value rows within the declared bound (exact when
    lossless)."""
    rng = np.random.default_rng(seed)
    stats = _rand_stats(rng, occupied=occupied)
    for spec in ALL_SPECS:
        _check_roundtrip_laws(spec, stats)


def _check_roundtrip_laws(spec, stats):
    codec = wirecodec.resolve_codec(spec).for_stream()
    decoded, nbytes = wirecodec.roundtrip(codec, stats)
    assert nbytes > 0
    lossless = spec in LOSSLESS_SPECS
    for col in stats:
        ms, md = stats[col]["moments"], decoded[col]["moments"]
        # the rows every bound / fpc / HT weight reads are always exact
        np.testing.assert_array_equal(np.asarray(ms.n), np.asarray(md.n))
        np.testing.assert_array_equal(np.asarray(ms.total), np.asarray(md.total))
        bins_s = np.asarray(stats[col]["sketch"].bins)
        bins_d = np.asarray(decoded[col]["sketch"].bins)
        # per-stratum sketch mass is exact under every codec (top-k
        # spreads integer residuals; quantize never touches bins)
        np.testing.assert_array_equal(bins_s.sum(axis=1), bins_d.sum(axis=1))
        if spec.startswith("topk"):
            np.testing.assert_array_equal(
                np.asarray(ms.wsum), np.asarray(md.wsum)
            )
        if lossless:
            _assert_tree_equal(stats[col], decoded[col], f"{spec}:{col}")
        elif spec.startswith("quantize"):
            qmax = {"quantize16": 32764, "quantize8": 124}[spec]
            for name in ("wsum", "m2"):
                a = np.asarray(getattr(ms, name))
                b = np.asarray(getattr(md, name))
                finite = np.isfinite(a)
                amax = float(np.abs(a[finite]).max()) if finite.any() else 0.0
                # declared half-step bound, plus one f32 ulp of the
                # reconstructed value for the final rounding
                bound = 0.5 * (amax / qmax if amax > 0 else 1.0) * (
                    1 + 1e-6
                ) + amax * 2e-7 + 1e-6
                assert np.abs(a - b).max() <= bound, (spec, col, name)
            # mean is recomputed from exact n + reconstructed wsum
            md_mean = np.asarray(md.mean)
            assert np.isfinite(md_mean[np.asarray(ms.n) > 0]).all()


@pytest.mark.parametrize("bits,qmax", ((16, 32764), (8, 124)))
def test_quantize_extrema_sentinels_and_bound(bits, qmax):
    """±inf identity lattice values ride dedicated sentinels (never a
    saturated finite code); finite extrema honor the half-step bound."""
    rng = np.random.default_rng(7)
    stats = _rand_stats(rng, occupied=4)
    codec = wirecodec.QuantizeCodec(bits)
    decoded, _ = wirecodec.roundtrip(codec, stats)
    for col in stats:
        es, ed = stats[col]["extrema"], decoded[col]["extrema"]
        for name in ("min", "max"):
            a = np.asarray(getattr(es, name))
            b = np.asarray(getattr(ed, name))
            np.testing.assert_array_equal(np.isposinf(a), np.isposinf(b))
            np.testing.assert_array_equal(np.isneginf(a), np.isneginf(b))
            finite = np.isfinite(a)
            amax = float(np.abs(a[finite]).max())
            bound = 0.5 * amax / qmax * (1 + 1e-6) + amax * 2e-7 + 1e-6
            assert np.abs(a[finite] - b[finite]).max() <= bound


def test_topk_sketch_totals_and_range():
    """Top-k keeps the k heaviest bins verbatim, confines the residual to
    the occupied [lo, hi] span, and preserves stratum totals exactly."""
    rng = np.random.default_rng(11)
    stats = _rand_stats(rng, occupied=6)
    codec = wirecodec.TopKSketchCodec(4)
    decoded, nb_topk = wirecodec.roundtrip(codec, stats)
    _, nb_sparse = wirecodec.roundtrip(wirecodec.SparseCodec(), stats)
    assert nb_topk < nb_sparse  # the whole point: fewer bins on the wire
    for col in stats:
        a = np.asarray(stats[col]["sketch"].bins)
        b = np.asarray(decoded[col]["sketch"].bins)
        np.testing.assert_array_equal(a.sum(axis=1), b.sum(axis=1))
        for r in range(a.shape[0]):
            nz = np.flatnonzero(a[r])
            if not len(nz):
                np.testing.assert_array_equal(b[r], 0.0)
                continue
            lo, hi = nz[0], nz[-1]
            assert not b[r, :lo].any() and not b[r, hi + 1 :].any(), r
            top = nz[np.argsort(-a[r][nz], kind="stable")][: codec.k]
            np.testing.assert_array_equal(a[r][np.sort(top)], b[r][np.sort(top)])
        # non-sketch rows ride the sparse path bit-exactly
        _assert_tree_equal(stats[col]["moments"], decoded[col]["moments"])
        _assert_tree_equal(stats[col]["extrema"], decoded[col]["extrema"])


def test_delta_stream_frames_and_reference_guard():
    """A delta stream opens with a keyframe, ships cheap XOR frames for
    slowly-changing panes, stays lossless across the sequence, re-keys
    after reset(), and refuses a delta with no reference frame."""
    rng = np.random.default_rng(3)
    base = _rand_stats(rng, occupied=4)
    drift = _rand_stats(np.random.default_rng(4), occupied=4)
    enc = wirecodec.resolve_codec("delta").for_stream()
    frames = []
    for stats in (base, base, drift, base):
        payload = enc.encode(wirecodec.flatten_stats(stats))
        frames.append(payload)
        decoded = wirecodec.unflatten_stats(enc.decode(payload))
        _assert_tree_equal(stats, decoded)
    assert [f.frame for f in frames] == ["key", "delta", "delta", "delta"]
    # an unchanged pane XORs to all-zero rows: near-free on the wire
    assert frames[1].nbytes < frames[0].nbytes
    enc.reset()
    payload = enc.encode(wirecodec.flatten_stats(base))
    assert payload.frame == "key"
    fresh = wirecodec.resolve_codec("delta").for_stream()
    delta_frame = next(f for f in frames if f.frame == "delta")
    with pytest.raises(ValueError, match="keyframe"):
        fresh.decode(delta_frame)


def _row(name, vals, identity=0.0, quantize_ok=False):
    return wirecodec.Row(
        column="c", kind="moments", name=name,
        array=np.asarray(vals, np.float32), quantize_ok=quantize_ok,
        identity=identity,
    )


def _assert_rows_bit_equal(expected, decoded, msg=""):
    for e, d in zip(expected, decoded):
        np.testing.assert_array_equal(
            np.asarray(e.array, np.float32).view(np.uint32),
            np.asarray(d.array, np.float32).view(np.uint32),
            err_msg=f"{msg}:{e.name}",
        )


def test_delta_exact_sign_flip_lossless():
    """Regression: an exact negation (cur == -prev) XORs to the -0.0 bit
    pattern, which a float occupancy test drops as unoccupied — the
    decoder would reconstruct the *old* value and the DPCM stream would
    diverge permanently (5 -> -5 -> 7 decoding as 5 -> 5 -> -7).  Bitwise
    occupancy must ship it.  Covers the extrema variant too: a ``min``
    row's +inf identity flipping to -inf is the same single-bit XOR."""
    codec = wirecodec.DeltaCodec()
    inf = np.inf
    seq = [
        [_row("wsum", [5.0, 0.0, 2.0]), _row("min", [inf, inf], identity=inf)],
        [_row("wsum", [-5.0, 0.0, 2.0]), _row("min", [-inf, inf], identity=inf)],
        [_row("wsum", [7.0, 0.0, 2.0]), _row("min", [-inf, inf], identity=inf)],
    ]
    for i, rows in enumerate(seq):
        payload = codec.encode(rows)
        assert payload.frame == ("key" if i == 0 else "delta")
        _assert_rows_bit_equal(rows, codec.decode(payload), f"frame{i}")


def test_sparse_roundtrip_preserves_sign_of_zero():
    """Regression: a stored -0.0 compares float-equal to the +0.0
    identity; the advertised bit-exact round-trip must keep its sign bit
    (bitwise occupancy), not decode it as +0.0."""
    rows = [_row("wsum", [-0.0, 0.0, 3.0])]
    decoded = wirecodec.SparseCodec().decode(wirecodec.SparseCodec().encode(rows))
    _assert_rows_bit_equal(rows, decoded)
    assert np.signbit(decoded[0].array[0]) and not np.signbit(decoded[0].array[1])


@pytest.mark.parametrize("bits", (16, 8))
def test_quantize_subnormal_amax_scale_floor(bits):
    """Regression: a subnormal amax underflows the f32 scale amax/qmax to
    0 — division by zero, every value clips to qmax and decodes to 0, and
    the declared half-step bound reads 0.  The scale must floor at the
    smallest normal f32 and the declared bound must still hold."""
    amax = float(np.float32(4e-45))  # subnormal; /qmax underflows to 0.0
    rows = [_row("wsum", [amax, 0.0], quantize_ok=True)]
    codec = wirecodec.QuantizeCodec(bits)
    with np.errstate(divide="raise"):
        payload = codec.encode(rows)
    tag, meta, _ = payload.entries[0]
    assert meta[0] == "quant" and meta[2] > 0  # declared bound scale/2 > 0
    decoded = codec.decode(payload)[0].array
    assert np.isfinite(decoded).all()
    assert abs(float(decoded[0]) - amax) <= meta[2]


def test_module_level_restore_reopens_delta_streams(table, panes):
    """Regression: ``checkpoint.restore`` called directly (not through
    ``StreamSession.restore``) must also drop per-stream DPCM state, so
    the first post-restore pane ships a keyframe instead of diffing
    against a reference frame the restored stream never saw."""
    pipe = EdgeCloudPipeline(
        table, PipelineConfig(raw_capacity=PANE, uplink_codec="delta")
    )
    sess = StreamSession(pipe)
    reg = sess.register(Query(aggs=(AggSpec("mean", "value"),)))
    sess.step(jax.random.key(0), panes[0])
    snap = checkpoint.snapshot(sess)
    sess.step(jax.random.key(1), panes[1])  # advances the DPCM reference
    assert any(grp._codec for grp in sess._fusion_groups.values())
    checkpoint.restore(sess, snap)
    assert all(grp._codec == {} for grp in sess._fusion_groups.values())
    # the re-keyed stream still serves lossless estimates
    step = sess.step(jax.random.key(1), panes[1])
    est = step.results[reg.qid].estimates["mean_value"]
    assert np.isfinite(float(est.value))


def test_resolve_codec_specs():
    assert wirecodec.resolve_codec(None) is None
    assert isinstance(wirecodec.resolve_codec("sparse"), wirecodec.SparseCodec)
    assert isinstance(wirecodec.resolve_codec("delta"), wirecodec.DeltaCodec)
    assert isinstance(wirecodec.resolve_codec("delta:sparse"), wirecodec.DeltaCodec)
    assert wirecodec.resolve_codec("topk12").k == 12
    assert wirecodec.resolve_codec("quantize8").bits == 8
    inst = wirecodec.SparseCodec()
    assert wirecodec.resolve_codec(inst) is inst
    for bad in ("gzip", "topk0", "quantize4", 3):
        with pytest.raises(ValueError):
            wirecodec.resolve_codec(bad)
    with pytest.raises(ValueError):
        PipelineConfig(uplink_codec="gzip")  # validated at config time


# -- engine integration: parity with the dense uplink --------------------------


@pytest.mark.parametrize("spec", LOSSLESS_SPECS)
def test_execute_parity_lossless(table, window, spec):
    """One-shot execute under a lossless codec: estimates, bounds, and
    counters bit-identical to the dense uplink; comm_bytes becomes the
    (much smaller) measured encoded size."""
    q = Query(
        aggs=(AggSpec("mean", "value"), AggSpec("var", "value"),
              AggSpec("p50", "value"), AggSpec("max", "value")),
        group_by="neighborhood",
    )
    pipe0 = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE))
    pipe1 = EdgeCloudPipeline(
        table, PipelineConfig(raw_capacity=PANE, uplink_codec=spec)
    )
    r0 = pipe0.execute(q, jax.random.key(3), window, fraction=0.5)
    r1 = pipe1.execute(q, jax.random.key(3), window, fraction=0.5)
    for k in r0.estimates:
        for field in EXACT_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(r0.estimates[k], field)),
                np.asarray(getattr(r1.estimates[k], field)),
                err_msg=f"{spec}:{k}.{field}",
            )
    dense = aqp.preagg_bytes(pipe0.plan(q), table.num_slots)
    assert int(r0.comm_bytes) == dense
    assert 0 < int(r1.comm_bytes) < dense


def test_session_fused_refined_cross_roi_parity(table, panes):
    """The full fused-session surface under a lossless codec — divergent
    fractions (refined per-member passes), cross-ROI Bernoulli fusion, and
    a multi-pane sliding window — emits estimates bit-identical to the
    dense session."""
    roi_south = ((22.45, 22.65), (113.76, 114.64))
    roi_north = ((22.60, 22.86), (113.76, 114.64))
    q_lo = Query(aggs=(AggSpec("mean", "value"), AggSpec("p50", "value")))
    q_hi = Query(aggs=(AggSpec("var", "value"),))
    q_roi = Query(aggs=(AggSpec("mean", "value"),), method="bernoulli", roi=roi_south)
    q_roi2 = Query(aggs=(AggSpec("sum", "occupancy", name="s"),),
                   method="bernoulli", roi=roi_north)

    def drive(cfg):
        pipe = EdgeCloudPipeline(table, cfg)
        sess = StreamSession(pipe)
        regs = [
            sess.register(q_lo, initial_fraction=0.3),
            sess.register(q_hi, initial_fraction=0.8),
            sess.register(q_roi, initial_fraction=0.5),
            sess.register(q_roi2, initial_fraction=0.6),
            sess.register(
                Query(aggs=(AggSpec("mean", "value"),)),
                window=WindowSpec("sliding", size=2),
            ),
        ]
        steps = [
            sess.step(jax.random.fold_in(jax.random.key(9), i), p)
            for i, p in enumerate(panes)
        ]
        return [r.qid for r in regs], steps

    qids0, steps0 = drive(PipelineConfig(raw_capacity=PANE))
    qids1, steps1 = drive(PipelineConfig(raw_capacity=PANE, uplink_codec="sparse"))
    assert qids0 == qids1
    for s0, s1 in zip(steps0, steps1):
        assert set(s0.results) == set(s1.results)
        for qid in s0.results:
            r0, r1 = s0.results[qid], s1.results[qid]
            for k in r0.estimates:
                for field in EXACT_FIELDS:
                    np.testing.assert_array_equal(
                        np.asarray(getattr(r0.estimates[k], field)),
                        np.asarray(getattr(r1.estimates[k], field)),
                        err_msg=f"{qid}:{k}.{field}",
                    )
            assert int(r1.comm_bytes) < int(r0.comm_bytes)


def test_raw_mode_untouched_by_codec(table, window):
    """Raw-mode uplinks ship tuples, not sufficient statistics: a
    configured codec must neither touch their results nor their analytic
    byte accounting."""
    q = Query(aggs=(AggSpec("mean", "value"),), mode="raw")
    pipe0 = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE))
    pipe1 = EdgeCloudPipeline(
        table, PipelineConfig(raw_capacity=PANE, uplink_codec="sparse")
    )
    r0 = pipe0.execute(q, jax.random.key(1), window, fraction=0.5)
    r1 = pipe1.execute(q, jax.random.key(1), window, fraction=0.5)
    assert int(r0.comm_bytes) == int(r1.comm_bytes) == aqp.raw_bytes(
        pipe0.plan(q), PANE
    )
    for field in EXACT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(r0.estimates["mean_value"], field)),
            np.asarray(getattr(r1.estimates["mean_value"], field)),
        )


# -- hardened byte accounting --------------------------------------------------


def test_sliding_comm_is_newly_shipped_bytes(table, panes):
    """Per-window comm reports bytes *newly shipped* since the previous
    emit — overlapped panes are not re-billed — so sliding and tumbling
    windows account identical totals over the same span."""
    def total_comm(win_spec):
        pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE))
        sess = StreamSession(pipe)
        reg = sess.register(
            Query(aggs=(AggSpec("mean", "value"),)), window=win_spec
        )
        emitted = 0
        for i, p in enumerate(panes):
            step = sess.step(jax.random.fold_in(jax.random.key(2), i), p)
            if reg.qid in step.results:
                emitted += int(step.results[reg.qid].comm_bytes)
        return emitted, sess.total_comm_bytes

    slide, slide_total = total_comm(WindowSpec("sliding", size=2))
    tumble, tumble_total = total_comm(WindowSpec("tumbling", size=1))
    assert slide == tumble == slide_total == tumble_total
    # and the dense model agrees: 4 panes, one fixed-size frame each
    pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE))
    per_pane = aqp.preagg_bytes(
        pipe.plan(Query(aggs=(AggSpec("mean", "value"),))), table.num_slots
    )
    assert tumble == per_pane * len(panes)


def test_comm_counters_exact_past_2p31(table, panes, tmp_path):
    """Cumulative and per-window byte counters are Python ints: forcing a
    near-2^31 carry-in must come out exactly (no int32 wrap, no float
    rounding) and survive the checkpoint round-trip."""
    pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE))
    sess = StreamSession(pipe)
    reg = sess.register(Query(aggs=(AggSpec("mean", "value"),)))
    sess.step(jax.random.key(0), panes[0])
    per_pane = sess.total_comm_bytes
    assert isinstance(per_pane, int) and per_pane > 0
    carry = 2**31 - 8  # an int32 accumulator would wrap on the next pane
    sess.total_comm_bytes += carry
    reg.pending_comm += carry
    step = sess.step(jax.random.key(1), panes[1])
    got = step.results[reg.qid].comm_bytes
    assert int(got) == carry + per_pane > 2**31
    assert sess.total_comm_bytes == carry + 2 * per_pane > 2**31
    # checkpoint round-trip keeps the exact values
    path = tmp_path / "big_comm.npz"
    checkpoint.save(checkpoint.snapshot(sess), path)
    pipe2 = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE))
    sess2 = StreamSession(pipe2)
    reg2 = sess2.register(Query(aggs=(AggSpec("mean", "value"),)))
    checkpoint.restore(sess2, checkpoint.load(path))
    assert sess2.total_comm_bytes == sess.total_comm_bytes
    assert reg2.pending_comm == reg.pending_comm == 0  # reset at the emit


def test_checkpoint_codec_fingerprint_guard(table, panes, tmp_path):
    """A snapshot refuses to restore under a different uplink codec (the
    byte accounting would silently change meaning), while pre-codec
    snapshots — no fingerprint, no pending_comm — still restore."""
    pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE, uplink_codec="sparse"))
    sess = StreamSession(pipe)
    sess.register(Query(aggs=(AggSpec("mean", "value"),)))
    sess.step(jax.random.key(0), panes[0])
    snap = checkpoint.snapshot(sess)
    assert snap["uplink_codec"] == "sparse"

    plain = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE))
    sess_plain = StreamSession(plain)
    sess_plain.register(Query(aggs=(AggSpec("mean", "value"),)))
    with pytest.raises(ValueError, match="uplink codec"):
        checkpoint.restore(sess_plain, snap)

    # forward-compat: an old snapshot without the additive fields restores,
    # reconstructing pending_comm from the ring
    legacy = checkpoint.snapshot(sess_plain)
    del legacy["uplink_codec"]
    for rec in legacy["registrations"]:
        del rec["pending_comm"]
    sess_plain2 = StreamSession(
        EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE))
    )
    reg2 = sess_plain2.register(Query(aggs=(AggSpec("mean", "value"),)))
    checkpoint.restore(sess_plain2, legacy)
    assert reg2.pending_comm == 0  # tumbling-1: nothing pending post-emit


# -- empty / all-overflow quantiles through the session ------------------------


def test_empty_and_overflow_quantiles_surface_nan(table):
    """A quantile of an empty histogram is NaN with infinite relative
    error — never a silent 0.  Covers both the fully-empty pane and the
    all-overflow pane (every tuple outside the stratum table, zeroed by
    zero_overflow) through StreamSession, grouped and ungrouped."""
    n = 512
    rng = np.random.default_rng(0)

    def pane(lat, lon):
        return windows.WindowBatch(
            sensor_id=np.zeros(n, np.int32),
            timestamp=np.zeros(n, np.float32),
            lat=np.full(n, lat, np.float32),
            lon=np.full(n, lon, np.float32),
            value=rng.normal(40, 12, n).astype(np.float32),
            valid=np.ones(n, bool),
        )

    empty = windows.WindowBatch(
        sensor_id=np.zeros(n, np.int32),
        timestamp=np.zeros(n, np.float32),
        lat=np.zeros(n, np.float32),
        lon=np.zeros(n, np.float32),
        value=np.zeros(n, np.float32),
        valid=np.zeros(n, bool),
    )
    overflow = pane(lat=0.0, lon=0.0)  # far outside the Shenzhen bbox

    pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=n))
    for win in (empty, overflow):
        sess = StreamSession(pipe)
        r_flat = sess.register(Query(aggs=(AggSpec("p50", "value"),)))
        r_grp = sess.register(
            Query(aggs=(AggSpec("p99", "value"),), group_by="neighborhood")
        )
        step = sess.step(jax.random.key(1), win)
        est = step.results[r_flat.qid].estimates["p50_value"]
        assert np.isnan(float(est.value))
        assert np.isinf(float(est.relative_error))
        grp = step.results[r_grp.qid].estimates["p99_value"]
        assert np.isnan(np.asarray(grp.value)).all()
        assert np.isinf(np.asarray(grp.relative_error)).all()
        # the interval fields themselves never go NaN
        for field in ("moe", "ci_low", "ci_high"):
            assert not np.isnan(np.asarray(getattr(grp, field))).any(), field


# -- multi-device: decode(psum(encode)) on the 8-device mesh -------------------


@pytest.mark.xdist_group("subprocess-heavy")
def test_sharded_psum_codec_parity_8dev():
    """execute_sharded under the sparse codec: the decoded post-psum
    states and every estimate are bit-identical to the dense sharded run
    (the codec sits after the collective, so cross-shard merge order is
    untouched), and the encoded frame is smaller than the dense model."""
    import os
    import subprocess
    import sys

    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import (
    SHENZHEN_BBOX, AggSpec, EdgeCloudPipeline, PipelineConfig, Query,
    make_table, query as aqp, windows,
)
from repro.data.streams import shenzhen_taxi_stream
from repro.launch.mesh import compat_make_mesh

assert jax.device_count() == 8
mesh = compat_make_mesh((8,), ("data",))
table = make_table(*SHENZHEN_BBOX, precision=5)
window = next(windows.count_windows(shenzhen_taxi_stream(num_chunks=2, seed=0), 32_768))
q = Query(aggs=(AggSpec("mean", "value"), AggSpec("p50", "value"), AggSpec("max", "value")))
pipe0 = EdgeCloudPipeline(table, PipelineConfig(), mesh=mesh)
pipe1 = EdgeCloudPipeline(table, PipelineConfig(uplink_codec="sparse"), mesh=mesh)
r0 = pipe0.execute_sharded(q, jax.random.key(1), window, fraction=0.7)
r1 = pipe1.execute_sharded(q, jax.random.key(1), window, fraction=0.7)
for k in r0.estimates:
    for field in ("value", "moe", "ci_low", "ci_high", "relative_error", "n", "population"):
        np.testing.assert_array_equal(
            np.asarray(getattr(r0.estimates[k], field)),
            np.asarray(getattr(r1.estimates[k], field)), err_msg=f"{k}.{field}")
for la, lb in zip(jax.tree.leaves(r0.stats), jax.tree.leaves(r1.stats)):
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
dense = aqp.preagg_bytes(pipe0.plan(q), table.num_slots)
assert 0 < int(r1.comm_bytes) < dense
print("SHARDED_CODEC_OK", int(r1.comm_bytes), dense)
"""
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=900,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-2000:])
    assert "SHARDED_CODEC_OK" in r.stdout


# -- the AbsSum-style pluggability contract ------------------------------------


def test_plugin_kind_rides_the_codec(table):
    """A registered third-party kind with payload hooks flows through the
    sparse codec untouched — the EDG003-enforced contract in action."""
    from repro.core.estimators import Accumulator, register_accumulator, ACCUMULATORS

    class BitSum(Accumulator):
        kind = "_test_codec_bitsum"

        def accumulate(self, values, stratum_idx, mask, num_slots, counts=None):
            w = jnp.where(mask, jnp.abs(values), 0.0)
            return jax.ops.segment_sum(w, stratum_idx, num_segments=num_slots)

        def merge(self, a, b):
            return a + b

        def merge_panes(self, stacked):
            return stacked.sum(0)

        def psum(self, state, axis_names, shared=None):
            return state

        def zero_overflow(self, state):
            keep = jnp.arange(state.shape[0]) < (state.shape[0] - 1)
            return jnp.where(keep, state, 0.0)

        def payload_vectors(self):
            return 1

        def payload_flatten(self, state):
            return (("s", state, True, 0.0),)

        def payload_unflatten(self, rows):
            return rows["s"]

        def template(self):
            return 0

    register_accumulator(BitSum())
    try:
        rng = np.random.default_rng(5)
        sidx = jnp.asarray(rng.integers(0, 10, 200), jnp.int32)
        vals = jnp.asarray(rng.normal(0, 3, 200), jnp.float32)
        mask = jnp.asarray(rng.random(200) < 0.5)
        stats = {
            "value": accumulate_column(
                ("moments", "_test_codec_bitsum"), vals, sidx, mask, 12
            )
        }
        decoded, nbytes = wirecodec.roundtrip(wirecodec.SparseCodec(), stats)
        _assert_tree_equal(stats, decoded)
        assert nbytes > 0
    finally:
        ACCUMULATORS.pop("_test_codec_bitsum", None)
