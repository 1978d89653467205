"""Streaming runtime: bounded-queue backpressure, pipelined parity, soak.

Covers the execution layer introduced with ``core/runtime.py``:

  * :class:`~repro.core.qdisc.BoundedPaneQueue` unit semantics — policies,
    decimation, close/drain, and the drop-ledger accounting chain;
  * **bit-parity**: with the lossless ``block`` policy and the shared
    ``fold_in(root, pane_index)`` key discipline, the pipelined runtime's
    emitted estimates are identical to a synchronous ``session.step`` loop,
    in preagg and raw modes, across sliding windows;
  * a **bursty soak**: >= 50 panes through a saturated 2-deep queue with
    mixed-method queries — the run completes, every shed tuple is accounted
    by cause end-to-end (queue ledger == session counters), and the
    estimates the runtime *did* emit stay within 10% MAPE of the exact
    per-pane answers at fraction 0.8;
  * **checkpoint with a non-empty ingest queue**: drain-then-snapshot makes
    the restored run bit-identical to one that never stopped;
  * count-triggered windows report an explicit ``n_dropped=0`` so drop
    counts sum cleanly across sources and causes;
  * event-driven sampling (decay / change trigger / heartbeat) and
    load-shedding hysteresis (enter high-water, exit low-water, fraction
    restore, deterministic decimation).
"""

import dataclasses

import numpy as np
import pytest

import jax

from repro.core import (
    SHENZHEN_BBOX,
    AggSpec,
    EdgeCloudPipeline,
    PipelineConfig,
    Query,
    RuntimeConfig,
    StreamRuntime,
    StreamSession,
    WindowSpec,
    feedback,
    make_table,
    windows,
)
from repro.core import runtime as rtm
from repro.core.qdisc import (
    CAUSE_QUEUE_FULL,
    CAUSE_SHED,
    BoundedPaneQueue,
    DropLedger,
    QueueClosed,
)
from repro.data.sources import BurstySource, PacedSource
from repro.data.streams import shenzhen_taxi_stream

PANE = 2_000
N_PANES = 8

EXACT_FIELDS = ("value", "moe", "ci_low", "ci_high", "relative_error", "n", "population")

Q_MEANVAR = Query(aggs=(AggSpec("mean", "value"), AggSpec("var", "value")))
Q_OCC = Query(aggs=(AggSpec("mean", "occupancy", name="occ"),))
Q_RAW = Query(aggs=(AggSpec("mean", "value"),), mode="raw")
Q_BERNOULLI = Query(aggs=(AggSpec("mean", "value"),), method="bernoulli")


@pytest.fixture(scope="module")
def table():
    return make_table(*SHENZHEN_BBOX, precision=5)


@pytest.fixture(scope="module")
def pipe(table):
    return EdgeCloudPipeline(table, PipelineConfig(raw_capacity=PANE))


@pytest.fixture(scope="module")
def panes():
    stream = shenzhen_taxi_stream(chunk_size=PANE, num_chunks=N_PANES, seed=0)
    return list(windows.count_windows(stream, PANE))[:N_PANES]


def _assert_steps_identical(expected, got):
    assert len(expected) == len(got)
    for e, g in zip(expected, got):
        assert e.pane_index == g.pane_index
        assert set(e.results) == set(g.results)
        assert e.fractions == g.fractions
        assert e.n_dropped == g.n_dropped
        assert e.drop_causes == g.drop_causes
        assert e.comm_bytes == g.comm_bytes
        for qid in e.results:
            re_, rg = e.results[qid], g.results[qid]
            assert set(re_.estimates) == set(rg.estimates)
            for k in re_.estimates:
                for field in EXACT_FIELDS:
                    np.testing.assert_array_equal(
                        np.asarray(getattr(re_.estimates[k], field)),
                        np.asarray(getattr(rg.estimates[k], field)),
                        err_msg=f"qid={qid} {k}.{field}",
                    )
            assert int(re_.n_sampled) == int(rg.n_sampled)
            assert int(re_.n_valid) == int(rg.n_valid)
            assert int(re_.n_dropped) == int(rg.n_dropped)


# -- qdisc: BoundedPaneQueue / DropLedger -------------------------------------


class _FakePane:
    """Host-only stand-in pane: just a size and upstream drop causes."""

    def __init__(self, size, drop_causes=None, tag=None):
        self.size = size
        self.drop_causes = drop_causes or {}
        self.tag = tag


def test_queue_validates_capacity_and_policy():
    with pytest.raises(ValueError, match="capacity"):
        BoundedPaneQueue(capacity=0)
    with pytest.raises(ValueError, match="policy"):
        BoundedPaneQueue(policy="drop-random")


def test_drop_newest_sheds_arrival_and_keeps_fifo_order():
    q = BoundedPaneQueue(capacity=2, policy="drop-newest")
    assert q.put(_FakePane(10, tag="a"))
    assert q.put(_FakePane(20, tag="b"))
    assert not q.put(_FakePane(30, tag="c"))  # full: arrival shed
    assert q.ledger.tuples == {CAUSE_QUEUE_FULL: 30}
    assert q.ledger.panes == {CAUSE_QUEUE_FULL: 1}
    assert [q.get(timeout=0).tag for _ in range(2)] == ["a", "b"]
    assert q.get(timeout=0) is None
    assert q.high_water == 2 and q.total_put == 2


def test_drop_oldest_evicts_head_to_admit_arrival():
    q = BoundedPaneQueue(capacity=2, policy="drop-oldest")
    q.put(_FakePane(10, tag="a"))
    q.put(_FakePane(20, tag="b"))
    assert q.put(_FakePane(30, tag="c"))  # evicts "a"
    assert q.ledger.tuples == {CAUSE_QUEUE_FULL: 10}
    assert [q.get(timeout=0).tag for _ in range(2)] == ["b", "c"]


def test_block_policy_times_out_into_a_counted_drop():
    q = BoundedPaneQueue(capacity=1, policy="block")
    assert q.put(_FakePane(5))
    assert not q.put(_FakePane(7), timeout=0.01)
    assert q.ledger.tuples == {CAUSE_QUEUE_FULL: 7}


def test_evicted_pane_upstream_drops_survive():
    """A shed pane's own ``late`` count must not vanish with it."""
    q = BoundedPaneQueue(capacity=1, policy="drop-newest")
    q.put(_FakePane(10))
    assert not q.put(_FakePane(30, drop_causes={"late": 7}))
    assert q.ledger.tuples == {CAUSE_QUEUE_FULL: 30, "late": 7}
    pending = q.take_drops()
    assert pending.tuples == {CAUSE_QUEUE_FULL: 30, "late": 7}
    assert not q.take_drops()  # drained


def test_decimation_admits_one_in_k_deterministically():
    q = BoundedPaneQueue(capacity=8, policy="drop-newest")
    q.set_decimation(3)
    admitted = [q.put(_FakePane(1, tag=i)) for i in range(9)]
    assert admitted == [True, False, False] * 3
    assert q.ledger.panes == {CAUSE_SHED: 6}
    q.set_decimation(0)
    assert q.put(_FakePane(1))


def test_close_drains_then_returns_none_and_rejects_puts():
    q = BoundedPaneQueue(capacity=4)
    q.put(_FakePane(1, tag="a"))
    q.close()
    assert q.get(timeout=0).tag == "a"  # queued panes still drain
    assert q.get(timeout=0) is None
    with pytest.raises(QueueClosed, match="closed"):
        q.put(_FakePane(2))


def test_drop_ledger_merge_and_totals():
    led = DropLedger()
    assert not led
    led.add("queue_full", 10)
    led.add("queue_full", 5, n_panes=2)
    led.merge_causes({"late": 3})
    assert led.tuples == {"queue_full": 15, "late": 3}
    assert led.panes == {"queue_full": 3}
    assert led.total_tuples == 18
    assert led


# -- runtime parity: pipelined == synchronous (lossless policy) ---------------


def _register_parity(sess):
    sess.register(Q_MEANVAR, window=WindowSpec("sliding", size=3))
    sess.register(Q_OCC)
    sess.register(Q_RAW, window=WindowSpec("tumbling", size=2))


def test_runtime_matches_synchronous_loop_bit_for_bit(pipe, panes):
    """Block policy + fold_in key discipline: the double-buffered, async
    runtime must emit exactly what a serial ``session.step`` loop does, in
    preagg and raw modes, across multi-pane windows."""
    root = jax.random.key(11)

    sess_sync = StreamSession(pipe, initial_fraction=0.8)
    _register_parity(sess_sync)
    sync = [
        sess_sync.step(jax.random.fold_in(root, i), p) for i, p in enumerate(panes)
    ]

    sess_rt = StreamSession(pipe, initial_fraction=0.8)
    _register_parity(sess_rt)
    rt = StreamRuntime(
        sess_rt, key=root, config=RuntimeConfig(queue_capacity=4, policy="block")
    )
    history = rt.run(panes)  # any iterable of panes is a Source

    _assert_steps_identical(sync, history)
    st = rt.stats()
    assert st.panes_processed == len(panes)
    assert st.panes_enqueued == len(panes)
    assert st.tuples_processed == sum(p.size for p in panes)
    assert st.dropped_tuples == 0 and st.dropped_tuples_by_cause == {}
    assert 0.0 < st.overlap_efficiency <= 1.0
    assert st.pane_latency["p99_ms"] >= st.pane_latency["p50_ms"] >= 0.0


def test_runtime_parity_under_paced_arrivals(pipe, panes):
    """Arrival timing must never leak into the answers: a jittered paced
    source produces the same history as back-to-back offers."""
    root = jax.random.key(12)

    sess_a = StreamSession(pipe, initial_fraction=0.8)
    sess_a.register(Q_MEANVAR)
    rt_a = StreamRuntime(sess_a, key=root, config=RuntimeConfig(policy="block"))
    hist_a = rt_a.run(panes[:4])

    sess_b = StreamSession(pipe, initial_fraction=0.8)
    sess_b.register(Q_MEANVAR)
    rt_b = StreamRuntime(sess_b, key=root, config=RuntimeConfig(policy="block"))
    hist_b = rt_b.run(PacedSource(panes[:4], mean_delay_s=0.002, jitter=0.5, seed=3))

    _assert_steps_identical(hist_a, hist_b)


def test_run_without_key_raises(pipe, panes):
    sess = StreamSession(pipe)
    sess.register(Q_MEANVAR)
    with pytest.raises(ValueError, match="PRNG key"):
        StreamRuntime(sess).run(panes[:1])


@pytest.mark.parametrize("exc", [ValueError, RuntimeError])
def test_source_error_mid_stream_is_reraised_after_queued_panes(pipe, panes, exc):
    """A source that fails mid-stream must not end the run as if the stream
    were over: the panes it delivered are processed, then ``run`` raises.
    RuntimeError is the case that matters: JAX raises its runtime errors
    as RuntimeErrors, and they must not pass for the queue-closed signal."""

    def failing_source():
        yield from panes[:2]
        raise exc("sensor feed lost")

    sess = StreamSession(pipe, initial_fraction=0.8)
    sess.register(Q_MEANVAR)
    rt = StreamRuntime(sess, key=jax.random.key(0), config=RuntimeConfig(policy="block"))
    with pytest.raises(exc, match="sensor feed lost"):
        rt.run(failing_source())
    assert len(rt.history) == 2
    assert rt.stats().panes_processed == 2


def test_offer_process_drain_are_incremental_and_bounded(pipe, panes):
    """Single-threaded driving: ``offer`` enqueues, ``process`` consumes
    what is queued *now*, ``drain`` is a full pipeline barrier."""
    sess = StreamSession(pipe, initial_fraction=0.8)
    sess.register(Q_MEANVAR)
    rt = StreamRuntime(
        sess, key=jax.random.key(13), config=RuntimeConfig(queue_capacity=8)
    )
    for p in panes[:3]:
        assert rt.offer(p)
    assert rt.queue.depth == 3
    steps = rt.process()
    assert len(steps) == 3 and rt.queue.depth == 0
    assert rt.process() == []  # nothing queued: no waiting, no new steps
    rt.drain()
    assert len(rt.history) == 3
    assert rt.stats().panes_processed == 3


# -- bursty soak: saturation, shed accounting, answer quality -----------------


def test_bursty_soak_completes_with_cause_accounted_drops(pipe, panes):
    """>= 50 bursty panes through a 2-deep drop-newest queue with mixed-
    method queries (SRS preagg, Bernoulli, raw): the run completes, every
    dropped tuple is accounted by cause through the whole chain (queue
    ledger -> step reports -> session counters), and the per-pane mean
    estimates that *were* emitted stay within 10% MAPE of exact.

    ``SOAK_REPEAT`` scales the run: PRs offer 60 panes (repeat=10); the
    nightly workflow sets 84 for a ~500-pane soak."""
    import os

    repeat = int(os.environ.get("SOAK_REPEAT", "10"))
    source = BurstySource(panes[:6], burst=10, gap_s=0.001, seed=2, repeat=repeat)
    n_offered = len(source.panes)
    assert n_offered >= 50

    sess = StreamSession(pipe, initial_fraction=0.8)
    q_mean = sess.register(Q_MEANVAR)
    sess.register(Q_BERNOULLI)
    sess.register(Q_RAW)

    processed = []  # exact ground truth: the panes the session really saw
    orig_step = sess.step

    def recording_step(key, pane):
        processed.append(pane)
        return orig_step(key, pane)

    sess.step = recording_step

    rt = StreamRuntime(
        sess,
        key=jax.random.key(21),
        config=RuntimeConfig(queue_capacity=2, policy="drop-newest"),
    )
    history = rt.run(source)
    st = rt.stats()

    # the run completed: every admitted pane was processed, and admissions
    # plus per-cause pane drops account for every arrival
    assert len(history) == len(processed) == st.panes_enqueued
    dropped_panes = sum(st.dropped_panes_by_cause.values())
    assert st.panes_enqueued + dropped_panes == n_offered
    assert st.dropped_panes_by_cause.get(CAUSE_QUEUE_FULL, 0) > 0  # saturated

    # tuple accounting chain: ledger == stats == session == per-step sums,
    # modulo drops still pending attachment after the final admitted pane
    assert st.dropped_tuples_by_cause == rt.queue.ledger.tuples
    assert sum(s.n_dropped for s in history) == sess.total_dropped
    remaining = rt.queue.take_drops()
    for cause, n in rt.queue.ledger.tuples.items():
        attached = sess.total_dropped_by_cause.get(cause, 0)
        assert attached + remaining.tuples.get(cause, 0) == n, cause
    assert sess.total_dropped == sum(sess.total_dropped_by_cause.values())

    # answer quality on what was emitted: exact per-pane means vs estimates
    errs = []
    for step, pane in zip(history, processed):
        exact = float(np.asarray(pane.value)[np.asarray(pane.valid)].mean())
        est = float(np.asarray(step.results[q_mean.qid].estimates["mean_value"].value))
        errs.append(abs(est - exact) / abs(exact))
    assert errs and float(np.mean(errs)) < 0.10


# -- checkpoint with a non-empty ingest queue ---------------------------------


def _register_ckpt(sess, mode):
    if mode == "preagg":
        sess.register(Q_MEANVAR, window=WindowSpec("sliding", size=3))
        sess.register(Q_OCC)
    else:
        sess.register(Q_RAW, window=WindowSpec("tumbling", size=2))


@pytest.mark.parametrize("mode", ["preagg", "raw"])
def test_checkpoint_with_queued_panes_is_bit_identical(pipe, panes, mode):
    """Drain-then-snapshot: checkpointing while panes sit in the ingest
    queue, restoring into a fresh session/runtime, and replaying the rest
    reproduces the uninterrupted run bit-for-bit (preagg AND raw)."""
    root = jax.random.key(33)
    cut = 5

    sess_full = StreamSession(pipe, initial_fraction=0.8)
    _register_ckpt(sess_full, mode)
    full = [
        sess_full.step(jax.random.fold_in(root, i), p) for i, p in enumerate(panes)
    ]

    sess_a = StreamSession(pipe, initial_fraction=0.8)
    _register_ckpt(sess_a, mode)
    rt_a = StreamRuntime(
        sess_a, key=root, config=RuntimeConfig(queue_capacity=8, policy="block")
    )
    for p in panes[:cut]:
        assert rt_a.offer(p)
    rt_a.process(max_panes=2)
    assert rt_a.queue.depth == 3  # the point of the test: queue is non-empty
    snap = rt_a.checkpoint()
    assert rt_a.queue.depth == 0 and sess_a.pane_index == cut

    sess_b = StreamSession(pipe, initial_fraction=0.8)
    _register_ckpt(sess_b, mode)
    sess_b.restore(snap)
    rt_b = StreamRuntime(
        sess_b, key=root, config=RuntimeConfig(queue_capacity=8, policy="block")
    )
    resumed = rt_b.run(panes[cut:])

    _assert_steps_identical(full, rt_a.history + resumed)


# -- drop accounting across sources and causes --------------------------------


def test_count_windows_report_explicit_zero_drops():
    stream = shenzhen_taxi_stream(chunk_size=PANE, num_chunks=2, seed=4)
    got = list(windows.count_windows(stream, PANE))
    assert got
    for pane in got:
        assert pane.n_dropped == 0
        assert pane.drop_causes == {}


def test_drops_sum_across_sources_and_causes(pipe, panes):
    """A pane carrying upstream ``late`` drops shed at a full queue: both
    its tuples (``queue_full``) and its prior ``late`` count must land in
    the session totals via the next admitted pane — and count-window panes
    contribute an explicit zero, so the totals are pure drop mass."""
    late_pane = dataclasses.replace(panes[1], n_dropped=7, drop_causes={"late": 7})
    sess = StreamSession(pipe, initial_fraction=0.8)
    sess.register(Q_MEANVAR)
    rt = StreamRuntime(
        sess,
        key=jax.random.key(5),
        config=RuntimeConfig(queue_capacity=1, policy="drop-newest"),
    )
    assert rt.offer(panes[0])
    assert not rt.offer(late_pane)  # shed at the full queue
    rt.process()
    rt.drain()
    assert sess.total_dropped == late_pane.size + 7
    assert sess.total_dropped_by_cause == {
        CAUSE_QUEUE_FULL: late_pane.size,
        "late": 7,
    }
    assert rt.history[0].n_dropped == sess.total_dropped


# -- event-driven sampling ----------------------------------------------------


def test_event_fraction_decays_boosts_and_heartbeats():
    pol = feedback.EventPolicy(
        heartbeat_panes=3, change_threshold=0.25, hot_fraction=0.8,
        idle_fraction=0.1, idle_decay=0.5,
    )
    state = feedback.EventState()
    # quiet panes decay geometrically toward the idle floor
    f = feedback.event_fraction(state, 0.01, 0.8, pol)
    assert f == pytest.approx(0.4) and state.quiet_panes == 1
    f = feedback.event_fraction(state, 0.01, f, pol)
    assert f == pytest.approx(0.2)
    # third quiet pane trips the heartbeat: probe hot, counters reset
    f = feedback.event_fraction(state, 0.01, f, pol)
    assert f == pol.hot_fraction and state.since_heartbeat == 0
    assert state.hot_panes == 1 and state.quiet_panes == 0
    # a change-score crossing boosts immediately; so does an inf score
    assert feedback.event_fraction(state, 0.30, 0.1, pol) == pol.hot_fraction
    assert feedback.event_fraction(state, float("inf"), 0.1, pol) == pol.hot_fraction
    # decay never undershoots the idle floor
    assert feedback.event_fraction(state, 0.0, 0.11, pol) == pytest.approx(0.1)


def test_change_score_semantics():
    same = feedback.change_score(np.array([1.0, 2.0]), np.array([1.0, 2.0]))
    assert float(same) == 0.0
    shift = feedback.change_score(np.array([1.0, 2.0]), np.array([1.5, 2.0]))
    assert float(shift) == pytest.approx(0.5)
    # nothing comparable -> inf: an unobservable stream must fail hot
    blind = feedback.change_score(np.array([np.nan]), np.array([1.0]))
    assert not np.isfinite(float(blind))


def test_watched_registration_decays_while_stream_is_quiet(pipe, panes):
    """With an unreachable change threshold and no heartbeat due, the
    watched fraction decays deterministically — scores are computed lazily
    on-device and applied one pane late, never stalling the loop."""
    sess = StreamSession(pipe, initial_fraction=0.8)
    reg = sess.register(Q_MEANVAR)
    rt = StreamRuntime(
        sess, key=jax.random.key(6), config=RuntimeConfig(policy="block")
    )
    pol = feedback.EventPolicy(
        heartbeat_panes=100, change_threshold=float("inf"), idle_decay=0.5,
        idle_fraction=0.1,
    )
    rt.watch(reg, policy=pol)
    rt.run(panes[:6])
    # scores mature one pane late: panes 1..4 produce the applied events
    state = rt._watches[reg.qid][3]
    assert state.hot_panes == 0 and state.quiet_panes == 4
    assert reg.fraction == pytest.approx(max(0.1, 0.8 * 0.5**4))


def test_watched_registration_heartbeats_back_to_hot(pipe, panes):
    sess = StreamSession(pipe, initial_fraction=0.8)
    reg = sess.register(Q_MEANVAR)
    rt = StreamRuntime(
        sess, key=jax.random.key(7), config=RuntimeConfig(policy="block")
    )
    pol = feedback.EventPolicy(
        heartbeat_panes=2, change_threshold=float("inf"), hot_fraction=0.7,
        idle_decay=0.5, idle_fraction=0.1,
    )
    rt.watch(reg, policy=pol)
    rt.run(panes[:6])
    # 4 applied events, every 2nd a heartbeat probe: quiet, hot, quiet, hot
    state = rt._watches[reg.qid][3]
    assert state.hot_panes == 2
    assert reg.fraction == pytest.approx(pol.hot_fraction)


# -- load shedding ------------------------------------------------------------


def test_load_shedding_hysteresis_and_fraction_restore(pipe, panes):
    """Depth >= high-water scales fractions down; depth <= low-water
    restores them — to ``max(current, saved)`` so a controller boost made
    *during* shedding survives the exit."""
    sess = StreamSession(pipe, initial_fraction=0.8)
    reg = sess.register(Q_MEANVAR)
    cfg = RuntimeConfig(
        queue_capacity=4, policy="block", load_shedding=True,
        shed_highwater=0.75, shed_lowwater=0.25, shed_fraction_scale=0.5,
    )
    rt = StreamRuntime(sess, key=jax.random.key(8), config=cfg)
    for p in panes[:4]:
        assert rt.offer(p)
    rt.process(max_panes=1)  # dispatch with depth 3 >= ceil(0.75*4): enter
    assert rt.shedding and rt.shed_panes >= 1
    assert reg.fraction == pytest.approx(0.4)
    reg.fraction = 0.9  # a controller raising the fraction mid-shed
    rt.drain()  # depth falls to the low-water mark: exit shed mode
    assert not rt.shedding
    assert reg.fraction == pytest.approx(0.9)  # max(current, saved) kept it
    assert len(rt.history) == 4


def test_load_shedding_decimation_drops_flow_as_shed_cause(pipe, panes):
    sess = StreamSession(pipe, initial_fraction=0.8)
    sess.register(Q_MEANVAR)
    cfg = RuntimeConfig(
        queue_capacity=2, policy="drop-newest", load_shedding=True,
        shed_highwater=0.5, shed_lowwater=0.0, shed_decimate=3,
    )
    rt = StreamRuntime(sess, key=jax.random.key(9), config=cfg)
    assert rt.offer(panes[0]) and rt.offer(panes[1])
    rt.process(max_panes=1)  # dispatch with depth 1 >= ceil(0.5*2): enter
    assert rt.shedding
    admitted = [rt.offer(p) for p in panes[2:8]]
    assert not all(admitted)  # decimation shed some arrivals
    assert rt.queue.ledger.tuples.get(CAUSE_SHED, 0) > 0
    rt.drain()  # empties the queue: low-water 0 exits shed mode
    assert not rt.shedding
    # shed tuples reached the session accounting via the next admitted pane
    assert sess.total_dropped_by_cause.get(CAUSE_SHED, 0) > 0
    # decimation was reset on exit: arrivals admit normally again
    assert rt.offer(panes[0]) and rt.offer(panes[1])


# -- stats helpers ------------------------------------------------------------


def _timing(t_dispatch, t_retired):
    return rtm.PaneTiming(
        pane_index=0, ingest_s=0.0, queue_wait_s=0.0, stage_s=0.0,
        dispatch_s=0.0, latency_s=t_retired - t_dispatch,
        t_dispatch=t_dispatch, t_retired=t_retired,
    )


def test_overlap_efficiency_interval_union():
    assert rtm._overlap_efficiency([]) == 0.0
    # back-to-back intervals: busy the whole wall
    assert rtm._overlap_efficiency([_timing(0, 1), _timing(1, 3)]) == pytest.approx(1.0)
    # a 1s gap in a 3s wall: 2/3 busy
    assert rtm._overlap_efficiency([_timing(0, 1), _timing(2, 3)]) == pytest.approx(2 / 3)
    # overlapping intervals never double-count
    assert rtm._overlap_efficiency([_timing(0, 2), _timing(1, 4)]) == pytest.approx(1.0)


def test_latency_percentiles_and_histogram():
    assert rtm._percentiles([]) == {
        "p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0
    }
    pct = rtm._percentiles([0.001, 0.002, 0.004])
    assert pct["p50_ms"] == pytest.approx(2.0)
    assert pct["max_ms"] == pytest.approx(4.0)
