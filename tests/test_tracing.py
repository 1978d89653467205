"""Host spans and counters of the pane loop (``core/spans.py``) and the
device side's program names and layer scopes.

  * the recorder: nesting, self time, counters, and nothing recorded where
    no recorder is active;
  * exact span and counter counts per pane on a tiny ``StreamRuntime`` run
    (a sliding-4 query and a refined SRS pair: two fusion groups);
  * compiles counted under the span that caused them, and none once warm;
  * the profiler's own trace: bare span names, ``pane`` as a stat,
    ``session.pass`` nested in ``runtime.dispatch`` on one thread;
  * ``jit_edge_pass`` / ``jit_refined_pass`` / ``jit_finalize`` /
    ``jit_finalize_batched`` modules with the layer scopes in their
    ``op_name`` metadata, single-device and sharded.
"""

import glob
import os
import subprocess
import sys
import threading

import pytest

import jax
import jax.numpy as jnp

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
    make_table,
    spans,
    windows,
)
from repro.data.streams import shenzhen_taxi_stream

PANE = 500
N_PANES = 6
NORTH = ((22.62, 22.87), (113.75, 114.65))
Q_SLIDE = Query(
    aggs=(
        AggSpec("mean", "value"),
        AggSpec("var", "value"),
        AggSpec("p50", "value"),
        AggSpec("max", "value"),
    )
)
Q_PAIR = Query(aggs=(AggSpec("mean", "value"),), roi=NORTH)

# the harness's own host spans (bench/): a program span of one of these
# names would be counted as the harness's
HARNESS_SPANS = {"session.step", "bench.window", "bench.warm", "loadgen.sleep", "sink.wait"}


@pytest.fixture(scope="module")
def table():
    return make_table(*SHENZHEN_BBOX, precision=5)


@pytest.fixture(scope="module")
def panes():
    stream = shenzhen_taxi_stream(chunk_size=PANE, num_chunks=N_PANES, seed=0)
    return list(windows.pane_windows(stream, pane_tuples=PANE))


def _session(pipe):
    """A sliding-4 query (shared pass) and a refined SRS pair (one group
    whose members run at 0.8 and 0.3, batched into one finalize)."""
    sess = StreamSession(pipe)
    slide = sess.register(Q_SLIDE, window=WindowSpec("sliding", size=4), initial_fraction=1.0)
    sess.register(Q_PAIR, initial_fraction=0.8)
    sess.register(Q_PAIR, initial_fraction=0.3)
    return sess, slide


def _stepping_clock(step=1.0):
    t = [0.0]

    def clock():
        t[0] += step
        return t[0]

    return clock


# -- the recorder -------------------------------------------------------------


def test_recorder_nests_spans_and_keeps_self_time():
    rec = spans.Recorder(_stepping_clock())
    with rec.active():
        with spans.span("outer", pane=0) as outer:  # t0 = 1
            with spans.span("inner"):  # 2 .. 3
                spans.count("c", 2)
            with rec.active(), spans.span("inner"):  # re-entrant: 4 .. 5
                spans.count("c")
        # outer closes at 6
    assert (outer.t0, outer.t1) == (1.0, 6.0)
    assert rec.spans() == {
        "outer": {"count": 1, "total_s": 5.0, "self_s": 3.0},
        "inner": {"count": 2, "total_s": 2.0, "self_s": 2.0},
    }
    assert rec.counters() == {"c": 3}


def test_inactive_recorder_records_nothing():
    clock = _stepping_clock()
    rec = spans.Recorder(clock)
    with spans.span("outer") as sp:
        spans.count("c")
    assert sp.t0 is None and sp.t1 is None
    assert clock() == 1.0  # never read
    with rec.active():
        pass
    with spans.span("after"):  # the block left the thread without a recorder
        spans.count("c")
    assert rec.spans() == {} and rec.counters() == {}


def test_recorder_totals_are_exact_across_threads():
    """The pane loop and the producer thread share one recorder: no update
    may be lost however often the interpreter switches threads."""
    rec = spans.Recorder(lambda: 0.0)
    n_threads, n_iter = 16, 10000

    def work():
        with rec.active():
            for _ in range(n_iter):
                with spans.span("s"):
                    spans.count("c")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert rec.spans()["s"]["count"] == n_threads * n_iter
    assert rec.counters() == {"c": n_threads * n_iter}


# -- the runtime's figures ------------------------------------------------------


def test_runtime_records_spans_and_counters_per_pane(table, panes):
    sess, slide = _session(EdgeCloudPipeline(table, PipelineConfig()))
    assert len(sess._fusion_groups) == 2
    rt = StreamRuntime(sess, key=jax.random.key(0), config=RuntimeConfig(policy="block"))
    rt.run(panes)
    st = rt.stats()
    n = len(panes)
    counts = {name: s["count"] for name, s in st.spans.items()}
    c = st.counters
    flushes = c.get("runtime.timeout_flushes", 0)
    assert counts == {
        "runtime.source": n + 1,  # every pane, then the end of the source
        "runtime.queue_put": n,
        "runtime.queue_get": n + 1 + flushes,  # every pane, timeouts, the close
        "runtime.stage": n,
        "runtime.dispatch": n,
        "session.pass": 2 * n,  # groups x panes
        "session.emit": n,
        "session.stack": n - 1,  # the sliding ring, from its second pane on
        "session.finalize": 2 * n,
        "runtime.retire": n,
    }
    # the sliding query's finalize and one batched finalize for the pair
    assert c["session.finalize_dispatches"] == 2 * n
    # from its second pane on the ring goes to the finalize program unstacked,
    # and the program stacks it, leaf by leaf
    assert c["session.program_stacked_windows"] == counts["session.stack"] == n - 1
    leaves = jax.tree.leaves(slide.ring[0].stats)
    assert c["session.host_stacked_leaves"] == (n - 1) * len(leaves)
    pane_bytes = sum(x.nbytes for x in leaves)
    assert c["session.stacked_bytes"] == sum(min(k, 4) for k in range(2, n + 1)) * pane_bytes
    assert "session.host_syncs" not in c  # no SLO, no codec: nothing blocks
    for s in st.spans.values():
        assert 0.0 <= s["self_s"] <= s["total_s"]
    d = st.spans["runtime.dispatch"]
    inner = st.spans["session.pass"]["total_s"] + st.spans["session.emit"]["total_s"]
    assert d["self_s"] == pytest.approx(d["total_s"] - inner)
    # PaneTiming reads the spans' own clock readings
    assert sum(t.dispatch_s for t in rt._timings) == pytest.approx(d["total_s"])


def test_compiles_counted_under_emit_until_ring_is_warm(table, panes):
    """A fresh pipeline compiles a finalize for each new ring length of the
    sliding query (1..4), under ``session.emit`` (in its ``session.finalize``
    child, the innermost span open); once the ring is full the same
    programs serve and nothing compiles."""
    sess, _ = _session(EdgeCloudPipeline(table, PipelineConfig()))
    rt = StreamRuntime(sess, key=jax.random.key(1), config=RuntimeConfig(policy="block"))
    per_pane, prev = [], {}
    for p in panes:
        rt.offer(p)
        rt.process()
        now = rt.stats().counters
        per_pane.append({k: v - prev.get(k, 0) for k, v in now.items()})
        prev = now
    emit = [sum(d.get(f"compiles.session.{n}", 0) for n in ("emit", "stack", "finalize"))
            for d in per_pane]
    assert all(e > 0 for e in emit[:4]), emit
    assert all(d.get("compiles.session.finalize", 0) > 0 for d in per_pane[:4]), per_pane
    assert emit[4:] == [0] * (len(panes) - 4), emit
    assert all(k.startswith(("compiles.", "cache_loads.", "session.", "runtime."))
               for k in prev)


def test_stack_and_finalize_spans_nest_inside_emit(table, panes):
    """``session.stack`` and ``session.finalize`` are the emit's children:
    the emit's self time is its total less theirs, neither holds a span of
    its own, and there is one ``session.finalize`` a finalize dispatch."""
    sess, _ = _session(EdgeCloudPipeline(table, PipelineConfig()))
    rt = StreamRuntime(sess, key=jax.random.key(4), config=RuntimeConfig(policy="block"))
    rt.run(panes)
    st = rt.stats()
    emit, stack, fin = (st.spans[f"session.{n}"] for n in ("emit", "stack", "finalize"))
    assert emit["self_s"] == pytest.approx(emit["total_s"] - stack["total_s"] - fin["total_s"])
    for s in (stack, fin):
        assert s["self_s"] == s["total_s"] and s["total_s"] <= emit["total_s"]
    assert fin["count"] == st.counters["session.finalize_dispatches"]


def test_stacked_bytes_of_a_geohash6_ring(panes):
    """At Geohash-6 a pane's sketch state is ``(6558, 513)`` float32 rows per
    sketched column; the counter adds every stacked leaf's bytes, from the
    shapes alone, so the pane whose ring holds four panes adds four panes'
    state."""
    table6 = make_table(*SHENZHEN_BBOX, precision=6)
    assert table6.num_slots == 6558
    pipe = EdgeCloudPipeline(table6, PipelineConfig())
    sess = StreamSession(pipe)
    slide = sess.register(Q_SLIDE, window=WindowSpec("sliding", size=4), initial_fraction=1.0)
    rt = StreamRuntime(sess, key=jax.random.key(5), config=RuntimeConfig(policy="block"))
    added, prev = [], 0
    for p in panes[:4]:
        rt.offer(p)
        rt.process()
        now = rt.stats().counters.get("session.stacked_bytes", 0)
        added.append(now - prev)
        prev = now
    leaves = jax.tree.leaves(slide.ring[0].stats)
    assert (6558, 513) in [x.shape for x in leaves]
    ring, counts = sess._window_ring(slide)
    program = pipe.finalize_fn(slide.plan, 4).lower(ring, counts, jax.random.key(5)).as_text()
    assert "tensor<4x6558x513xf32>" in program  # the program stacks the sketch ring
    stacked = jax.tree.leaves(ring)
    assert [x.shape for x in stacked].count((6558, 513)) >= 4
    assert added == [0] + [k * sum(x.nbytes for x in leaves) for k in (2, 3, 4)]
    assert added[3] == sum(x.nbytes for x in stacked) > 4 * 6558 * 513 * 4


def test_session_step_without_a_runtime_records_nothing(table, panes):
    """Stepping a session by hand, after a runtime has run it, adds nothing
    to that runtime's spans or counters."""
    sess, _ = _session(EdgeCloudPipeline(table, PipelineConfig()))
    rt = StreamRuntime(sess, key=jax.random.key(6), config=RuntimeConfig(policy="block"))
    rt.run(panes[:4])
    before = rt.stats()
    for i, p in enumerate(panes[4:]):
        sess.step(jax.random.key(i), p)
    after = rt.stats()
    assert sess.pane_index == len(panes)
    assert after.spans == before.spans and after.counters == before.counters
    assert "session.stack" in before.spans and "session.stacked_bytes" in before.counters


# -- the profiler's trace --------------------------------------------------------


def test_profiler_trace_holds_program_spans(table, panes, tmp_path):
    sess, _ = _session(EdgeCloudPipeline(table, PipelineConfig()))
    config = RuntimeConfig(policy="block")
    StreamRuntime(sess, key=jax.random.key(2), config=config).run(panes[:2])  # compiles
    rt = StreamRuntime(sess, key=jax.random.key(2), config=config)
    jax.profiler.start_trace(str(tmp_path))
    try:
        rt.run(panes[2:4])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    named = {}  # span name -> [(line, start, end, stats)]
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("runtime.", "session.")):
                    named.setdefault(e.name, []).append(
                        ((plane.name, li), e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                    )
    assert {"runtime.source", "runtime.queue_put", "runtime.queue_get", "runtime.stage",
            "runtime.dispatch", "runtime.retire", "session.pass", "session.emit",
            "session.stack", "session.finalize"} <= set(named)
    assert not HARNESS_SPANS & set(named)
    assert sorted(s["pane"] for *_, s in named["runtime.dispatch"]) == [2, 3]
    passes = named["session.pass"]
    assert len(passes) == 4
    assert {(s["pane"], s["group"], s["program"]) for *_, s in passes} == {
        (2, 0, "shared"), (2, 1, "refined"), (3, 0, "shared"), (3, 1, "refined")
    }
    for line, a, b, s in passes:
        outer = [d for d in named["runtime.dispatch"] if d[3]["pane"] == s["pane"]]
        assert len(outer) == 1
        assert outer[0][0] == line and outer[0][1] <= a and b <= outer[0][2]
    for name in ("session.stack", "session.finalize"):
        for line, a, b, s in named[name]:
            (outer,) = [e for e in named["session.emit"] if e[3]["pane"] == s["pane"]]
            assert outer[0] == line and outer[1] <= a and b <= outer[2]


# -- program names and layer scopes ----------------------------------------------


def _hlo(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


def _has_scope(text: str, program: str, scope: str) -> bool:
    """Some instruction's ``op_name`` lies under ``scope`` inside ``program``."""
    return any(
        f'op_name="jit({program})/' in ln and f"/{scope}/" in ln for ln in text.splitlines()
    )


def test_programs_carry_names_and_layer_scopes(table, panes):
    pipe = EdgeCloudPipeline(table, PipelineConfig())
    sess, slide = _session(pipe)
    key = jax.random.key(3)
    for i, p in enumerate(panes[:4]):
        sess.step(jax.random.fold_in(key, i), p)
    shared, refined = sess._fusion_groups.values()
    lat, lon, cols, valid = pipe._window_arrays(panes[0], shared.fused_plan().shared)

    text = _hlo(shared._pass_fn, key, lat, lon, cols, valid, jnp.float32(1.0))
    assert text.startswith("HloModule jit_edge_pass")
    for scope in ("stratify", "sample", "reduce"):
        assert _has_scope(text, "edge_pass", scope), scope
    assert not _has_scope(text, "edge_pass", "bounds")  # finalize's, not the pass's

    text = _hlo(refined._refined_fn, key, lat, lon, cols, valid,
                jnp.asarray([0.8, 0.3], jnp.float32))
    assert text.startswith("HloModule jit_refined_pass")
    for scope in ("stratify", "sample", "reduce"):
        assert _has_scope(text, "refined_pass", scope), scope

    ring, counts = sess._window_ring(slide)
    text = _hlo(pipe.finalize_fn(slide.plan, 4), ring, counts, key)
    assert text.startswith("HloModule jit_finalize,")
    for scope in ("merge_panes", "bounds"):
        assert _has_scope(text, "finalize", scope), scope

    pair = [r for r in sess.registrations if r is not slide]
    rings, counts = zip(*(sess._window_ring(r) for r in pair))
    text = _hlo(pipe.batched_finalize_fn(pair[0].plan, 1, 2), list(rings), list(counts), key)
    assert text.startswith("HloModule jit_finalize_batched")

    text = _hlo(pipe._query_fn(Q_SLIDE, sharded=False), key, lat, lon, cols, valid,
                jnp.float32(1.0))
    assert text.startswith("HloModule jit_execute")
    assert _has_scope(text, "execute", "bounds")


def test_sharded_edge_pass_carries_name_and_collective_scope():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.core import (SHENZHEN_BBOX, AggSpec, EdgeCloudPipeline, PipelineConfig, Query,
                        make_table, windows)
from repro.data.streams import shenzhen_taxi_stream
from repro.sharding.compat import compat_make_mesh

table = make_table(*SHENZHEN_BBOX, precision=5)
pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=256),
                         mesh=compat_make_mesh((4,), ("data",)))
(pane,) = windows.pane_windows(shenzhen_taxi_stream(chunk_size=1024, num_chunks=1, seed=0),
                               pane_tuples=1024)
for mode in ("preagg", "raw"):
    plan = pipe.plan(Query(aggs=(AggSpec("mean", "value"),), mode=mode))
    lat, lon, cols, valid = pipe._window_arrays(pane, plan)
    fn = pipe._pass_fn(plan, sharded=True)
    text = fn.lower(jax.random.key(0), lat, lon, cols, valid, jnp.float32(0.5)).compile().as_text()
    assert text.startswith("HloModule jit_edge_pass"), text[:80]
    ops = [ln for ln in text.splitlines() if "all-reduce(" in ln or "all-gather(" in ln]
    assert ops and all('op_name="jit(edge_pass)/' in ln and "/collective/" in ln
                       for ln in ops), ops
    print("SHARDED_OK", mode, len(ops))
"""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-2000:])
    assert "SHARDED_OK preagg" in r.stdout and "SHARDED_OK raw" in r.stdout
