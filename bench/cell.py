"""One benchmark cell: a deployment (``configs/<name>.json``) under a traffic
mix (``traffic/<name>.json``), driven through the system's own entry point.

The system under test is built exactly as a user builds it:
``EdgeCloudPipeline`` (the default ``PipelineConfig``, so the default edge
backend) -> ``StreamSession`` -> ``StreamRuntime.run``.  Around it the
harness adds three things of its own:

* a load generator, the iterable ``StreamRuntime.run`` pulls panes from.
  It replays the seeded stream as a ring (the paper's Kafka replay of a
  fixed data set), on an absolute schedule (``paced``: pane ``k`` is due
  when its last tuple is created at the offered tuple rate, whatever the
  system does) or as fast as the queue admits (``backlog``);
* a thin delegating wrapper around the session, handed to the runtime,
  that passes each emitted ``SessionStep`` to
* a sink thread, the client: it waits for each result and stamps when it
  was ready.  It adds no sync to the pane loop.

After the window the sink's sample of steps, drawn from the seed, is
compared with the NumPy reference (``reference.py``).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import queue
import threading
import time
from pathlib import Path

import numpy as np

from . import reference

# -- the cell's files -------------------------------------------------------------


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, workload: str) -> dict:
    """``BENCHMARK.json``'s entry for ``workload`` with its configuration
    and traffic files read: ``{"spec", "cell", "config", "traffic", "dir"}``,
    where ``dir`` is the benchmark's directory in that checkout."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    files = read_cell(root / spec["paths"][0], cell, root / configs[cell["config"]]["file"])
    return {"spec": spec, **files}


def read_cell(bench: Path, cell: dict, config_file: Path) -> dict:
    """A cell's configuration and traffic files, read:
    ``{"cell", "config", "traffic", "dir"}``."""
    config = json.loads(config_file.read_text())
    traffic = json.loads((bench / "traffic" / f"{cell['traffic']}.json").read_text())
    return {"cell": cell, "config": config, "traffic": traffic, "dir": bench}


# -- the stream, replayed ------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Pane:
    """Host arrays of one pane of the replay ring (built once per run)."""

    lat: np.ndarray
    lon: np.ndarray
    cols: dict
    batch: object  # WindowBatch


def make_pane_type():
    """A ``WindowBatch`` that also carries its replay position, due time and
    tuple count: the runtime's copies (``dataclasses.replace``) keep them, so
    the session wrapper can tell which pane a step served."""
    from repro.core.windows import WindowBatch

    @dataclasses.dataclass(frozen=True)
    class TimedPane(WindowBatch):
        pos: int = 0
        due: float = 0.0
        tuples: int = 0

    return TimedPane


class Replay:
    """The seeded stream as a ring, cut into panes of ``pane_tuples``: pane
    ``k`` holds tuples ``[k*P, (k+1)*P)`` modulo the stream's length, so a
    cycle has ``N / gcd(N, P)`` distinct panes."""

    def __init__(self, bench: Path, config: dict, pane_tuples: int, seed: int):
        gen = load_module(bench / "traffic" / f"{config['stream']['generator']}.py")
        params = {k: v for k, v in config["stream"].items() if k != "generator"}
        chunks = list(gen.stream(seed, config["bbox"], **params))
        data = {k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]}
        n = len(data["lat"])
        self.pane_tuples = pane_tuples
        self.period = n // math.gcd(n, pane_tuples)
        self.columns = [k for k in data if k not in ("sensor_id", "timestamp", "lat", "lon")]
        self._type = make_pane_type()
        self._panes = []
        for k in range(self.period):
            idx = (k * pane_tuples + np.arange(pane_tuples)) % n
            d = {key: v[idx] for key, v in data.items()}
            extra = {c: d[c] for c in self.columns if c != "value"}
            batch = self._type(
                sensor_id=d["sensor_id"], timestamp=d["timestamp"], lat=d["lat"],
                lon=d["lon"], value=d["value"], valid=np.ones(pane_tuples, bool), extra=extra,
                pos=k, tuples=pane_tuples,
            )
            self._panes.append(_Pane(d["lat"], d["lon"], {c: d[c] for c in self.columns}, batch))

    def pane(self, k: int, due: float):
        return dataclasses.replace(self._panes[k % self.period].batch, pos=k % self.period, due=due)

    def host(self, pos: int) -> tuple:
        p = self._panes[pos]
        return p.lat, p.lon, p.cols


# -- the load generator -----------------------------------------------------------


class Offer:
    """The iterable ``StreamRuntime.run`` pulls panes from.

    ``period_s`` set: pane ``k`` is due at ``t0 + (k + 1) * period_s`` and
    offered then, never earlier, however late the last one was (an open
    loop); without it every pane is due when offered.  Stops at ``t_end``
    or after ``count`` panes."""

    def __init__(self, replay: Replay, first: int, clock, *, t_end=None, count=None,
                 period_s=None, t0=None, span="bench.window"):
        self.replay, self.first, self.clock = replay, first, clock
        self.t_end, self.count, self.period_s, self.span = t_end, count, period_s, span
        self.t0 = t0
        self.lags: list[float] = []
        self.offered = 0

    def __iter__(self):
        import jax

        if self.t0 is None:
            self.t0 = self.clock()
        with jax.profiler.TraceAnnotation(self.span):
            k = 0
            while self.count is None or k < self.count:
                if self.period_s is not None:
                    due = self.t0 + (k + 1) * self.period_s
                    if self.t_end is not None and due > self.t_end:
                        return
                    wait = due - self.clock()
                    if wait > 0:
                        with jax.profiler.TraceAnnotation("loadgen.sleep"):
                            time.sleep(wait)
                else:
                    due = self.clock()
                    if self.t_end is not None and due >= self.t_end:
                        return
                self.lags.append(self.clock() - due)
                self.offered += 1
                yield self.replay.pane(self.first + k, due)
                k += 1


# -- the client: session wrapper and sink ------------------------------------------


@dataclasses.dataclass
class StepRecord:
    pane_index: int
    pos: int
    due: float
    tuples: int
    in_window: bool
    stamps: list  # sink's ready time of each result, in emit order
    dispatch_s: float


class Sink:
    """The client's thread: waits on every result of every emitted step and
    stamps when it was ready.  Keeps a sample of the window's steps, drawn
    from the seed (reservoir sampling), for the comparison; lets the rest
    go, so that device memory does not grow with the window."""

    def __init__(self, clock, keep: int, seed: int):
        self.clock = clock
        self.keep = keep
        self._rng = np.random.default_rng([seed, 2])
        self._q: queue.Queue = queue.Queue()
        self.records: list[StepRecord] = []
        self.sample: list = []  # (record, step)
        self._seen_window = 0
        self.runtime = None
        self._released = self._done = 0
        self.error: BaseException | None = None
        self._thread = threading.Thread(target=self._loop, name="bench-sink", daemon=True)
        self._thread.start()

    def put(self, step, record: StepRecord) -> None:
        self._q.put((step, record))

    def attach(self, runtime) -> None:
        """Follow a new runtime's history (call with the queue drained)."""
        self.runtime, self._released, self._done = runtime, 0, 0

    def _loop(self) -> None:
        import jax

        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                step, rec = item
                for res in step.results.values():
                    with jax.profiler.TraceAnnotation("sink.wait"):
                        jax.block_until_ready(res)
                    rec.stamps.append(self.clock())
                self.records.append(rec)
                if rec.in_window:
                    self._reservoir(rec, step)
                self._release()
            except BaseException as e:  # the thread's boundary: drain() re-raises it
                self.error = e
            finally:
                self._q.task_done()

    def _reservoir(self, rec, step) -> None:
        i = self._seen_window
        self._seen_window += 1
        if i < self.keep:
            self.sample.append((rec, step))
        else:
            j = int(self._rng.integers(0, i + 1))
            if j < self.keep:
                self.sample[j] = (rec, step)

    def _release(self) -> None:
        """Drop the runtime's references to the steps the client has read
        (its history lists them in the order the sink receives them)."""
        self._done += 1
        hist = self.runtime.history if self.runtime is not None else []
        for j in range(self._released, min(len(hist), self._done)):
            hist[j] = None
        self._released = max(self._released, min(len(hist), self._done))

    def drain(self) -> None:
        self._q.join()
        if self.error is not None:
            raise self.error

    def close(self) -> None:
        self._q.put(None)
        self._thread.join()


class ObservedSession:
    """Delegates to the session; hands each step to the sink.  The runtime
    sees the session's own ``step`` and attributes."""

    def __init__(self, session, sink: Sink, clock):
        self._session, self._sink, self._clock = session, sink, clock
        self.in_window = False

    def step(self, key, pane):
        import jax

        t0 = self._clock()
        with jax.profiler.TraceAnnotation("session.step"):
            step = self._session.step(key, pane)
        dt = self._clock() - t0
        # materialize batched results here, on the pane loop's thread: the
        # runtime reads them next (its retire markers), and the lazy mapping
        # is not safe to materialize from two threads at once
        step.results.values()
        rec = StepRecord(step.pane_index, pane.pos, pane.due, pane.tuples, self.in_window, [], dt)
        self._sink.put(step, rec)
        return step

    def __getattr__(self, name):
        return getattr(self._session, name)


# -- the system under test ---------------------------------------------------------


def build_query(q: dict):
    from repro.core import AggSpec, Query

    roi = q.get("roi")
    return Query(
        aggs=tuple(AggSpec(kind, col) for kind, col in q["aggs"]),
        group_by=q.get("group_by"),
        roi=tuple(tuple(r) for r in roi) if roi is not None else None,
        method=q.get("method", "srs"),
        mode=q.get("mode", "preagg"),
    )


def build_session(config: dict, traffic: dict, devices, pipeline_overrides=None):
    """The deployment as a user builds it: table, pipeline (one edge node per
    device of a mesh when ``edge_nodes`` > 1), session, registrations."""
    from repro.core import (
        EdgeCloudPipeline, PipelineConfig, StreamSession, WindowSpec, make_table,
    )

    table = make_table(*config["bbox"], precision=config["precision"],
                       neighborhood_precision=config["neighborhood_precision"])
    nodes = config["edge_nodes"]
    kwargs = dict(pipeline_overrides or {})
    mesh = None
    if nodes > 1:
        from repro.sharding.compat import compat_make_mesh

        if len(devices) < nodes:
            raise RuntimeError(f"{nodes} edge nodes need {nodes} devices; have {len(devices)}")
        mesh = compat_make_mesh((nodes,), ("data",))
        # a raw buffer as large as a node's share of the pane: nothing truncates
        kwargs.setdefault("raw_capacity", traffic["pane_tuples"] // nodes)
    pipe = EdgeCloudPipeline(table, PipelineConfig(**kwargs), mesh=mesh)
    sess = StreamSession(pipe, sharded=nodes > 1)
    regs = {}
    for q in traffic["queries"]:
        window = WindowSpec(**q["window"]) if "window" in q else None
        regs[q["name"]] = sess.register(build_query(q), window=window,
                                        initial_fraction=q["fraction"])
    return sess, regs


def precompile(sess, key, pane) -> int:
    """Compile each fusion group's pane program on a thread pool, as the
    session will dispatch it: the TPU compiler spends tens of seconds on
    each program that sorts, and compiles run in parallel across host cores.
    jit reuses these in process.  Returns the programs compiled; 0 where the
    session's layout is not the one this reads (the warm panes then compile
    each program on first dispatch)."""
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor

    try:
        pipe, lowered = sess.pipe, []
        for grp in sess._fusion_groups.values():
            fused = grp.fused_plan()
            fractions = [r.fraction for r in grp.members]
            lat, lon, cols, valid = pipe._window_arrays(pane, fused.shared)
            if sess._refines(fused, fractions):
                fn = grp._refined_fn = pipe._refined_pass_fn(fused, sess.sharded)
                frac = jnp.asarray(fractions, jnp.float32)
            else:
                fn = grp._pass_fn = pipe._pass_fn(fused.shared, sess.sharded)
                frac = jnp.float32(max(fractions))
            lowered.append(fn.lower(key, lat, lon, cols, valid, frac))
    except (AttributeError, TypeError) as e:
        print(f"precompile: skipped ({type(e).__name__}: {e})", flush=True)
        return 0
    with ThreadPoolExecutor(max_workers=min(len(lowered), os.cpu_count() or 1)) as pool:
        list(pool.map(lambda low: low.compile(), lowered))
    return len(lowered)


class CompileCounter:
    """Compiles and persistent-cache hits JAX reports, with their times."""

    def __init__(self, clock):
        import jax

        self.clock = clock
        self.compiles: list[float] = []
        self.cache_hits: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(self.clock())

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits.append(self.clock())

    def between(self, lo: float, hi: float) -> tuple[int, int]:
        return (sum(lo <= t <= hi for t in self.compiles),
                sum(lo <= t <= hi for t in self.cache_hits))


# -- one run --------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    """What one measured window saw, for the metric readers."""

    seconds: float
    setup_s: float
    t_start: float
    t_end: float
    records: list  # StepRecord of the window's steps
    offered: int
    dropped_panes: int
    lags: list
    runtime_stats: object  # RuntimeStats of the window's runtime
    chips: int
    trace: object = None  # trace.Reduced, with --trace 1
    memory_peak_bytes: int = 0

    def latencies_ms(self) -> np.ndarray:
        """Every result of the window: from its pane's due time (the creation
        of the pane's newest tuple) to the client's ready stamp."""
        return np.array([(t - r.due) * 1e3 for r in self.records for t in r.stamps])

    def completed_tuples(self) -> tuple[int, float]:
        """Tuples of the panes whose every result was ready inside the window,
        and the seconds from the window's start to the last of those panes'
        ready stamps (the window's length where none was ready)."""
        done = [(r.tuples, max(r.stamps)) for r in self.records
                if r.stamps and max(r.stamps) <= self.t_end]
        if not done:
            return 0, self.seconds
        return sum(n for n, _ in done), max(t for _, t in done) - self.t_start


def run_window(ctx: dict, seed: int, seconds: float, *, t_process: float, trace_dir=None,
               pipeline_overrides=None, log=print) -> tuple[Window, dict]:
    """Set up the cell, warm it, measure one window, and compare a sample of
    what the window produced with the reference.  Returns the window and the
    compared numbers (``reference.CHECKS`` plus ``results_missing`` and
    ``steps_checked``)."""
    import jax

    from repro.core import RuntimeConfig, StreamRuntime

    config, traffic = ctx["config"], ctx["traffic"]
    chips = ctx["cell"]["chips"]
    clock = time.perf_counter
    counter = CompileCounter(clock)
    devices = jax.devices()[:chips]
    pane_tuples = traffic["pane_tuples"]

    replay = Replay(ctx["dir"], config, pane_tuples, seed)
    sess, regs = build_session(config, traffic, devices, pipeline_overrides)
    key = jax.random.key(int(np.random.SeedSequence(seed).generate_state(1)[0]))
    n_pre = precompile(sess, jax.random.fold_in(key, 0), replay.pane(0, 0.0))
    sink = Sink(clock, traffic["check_steps"], seed)
    observed = ObservedSession(sess, sink, clock)
    rt_config = RuntimeConfig(policy=traffic["policy"], queue_capacity=traffic["queue_capacity"])

    # warm panes: every program of the window, the sliding ring's finalize
    # for each ring length among them, compiled or loaded before it opens
    warm = StreamRuntime(observed, key=key, config=RuntimeConfig(policy="block"))
    sink.attach(warm)
    offer = Offer(replay, 0, clock, count=traffic["warm_panes"], span="bench.warm")
    warm.run(offer)
    sink.drain()
    first = offer.offered
    del warm

    rt = StreamRuntime(observed, key=key, config=rt_config)
    sink.attach(rt)
    observed.in_window = True
    period = pane_tuples / traffic["tuples_per_s"] if traffic["arrival"] == "paced" else None
    t_start = clock()
    setup_s = t_start - t_process
    t_end = t_start + seconds
    offer = Offer(replay, first, clock, t_end=t_end, period_s=period, t0=t_start)
    if trace_dir is not None:
        from . import trace as tr

        tr.start(trace_dir)
    rt.run(offer)
    sink.drain()
    t_done = clock()
    reduced = None
    if trace_dir is not None:
        reduced = tr.stop_and_reduce(trace_dir, chips)
    hist = rt.history
    for j in range(len(hist)):
        hist[j] = None
    stats = rt.stats()
    compiles, hits = counter.between(t_start, t_done)
    log(f"window: {compiles} compiles and {hits} persistent-cache loads inside the window; "
        f"setup compiled {n_pre} pane programs on a thread pool and loaded "
        f"{counter.between(0.0, t_start)[1]} programs from the persistent cache", flush=True)
    window = Window(
        seconds=seconds, setup_s=setup_s, t_start=offer.t0, t_end=t_end,
        records=[r for r in sink.records if r.in_window], offered=offer.offered,
        dropped_panes=sum(stats.dropped_panes_by_cause.values()), lags=offer.lags,
        runtime_stats=stats, chips=chips, trace=reduced,
    )
    window.memory_peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )
    sample = sorted(sink.sample, key=lambda rs: rs[0].pane_index)
    sink.close()
    t_check = clock()
    checks = compare(sample, sess, regs, ctx, replay, sink.records)
    log(f"reference: {len(sample)} steps compared in {clock() - t_check:.1f} s", flush=True)
    return window, checks


def compare(sample, sess, regs, ctx, replay: Replay, records) -> dict:
    """The sampled steps' results against the reference of their windows."""
    import jax

    config, traffic = ctx["config"], ctx["traffic"]
    table = reference.Table(config["bbox"], config["precision"], config["neighborhood_precision"])
    pos_of = {r.pane_index: r.pos for r in records}
    checks = reference.empty_checks()
    missing = 0
    refs: dict = {}  # the replay repeats its panes, and so their references
    for rec, step in sample:
        for q in traffic["queries"]:
            reg = regs[q["name"]]
            if reg.qid not in step.results:
                missing += 1
                continue
            res = step.results[reg.qid]
            host = jax.device_get(res._replace(stats={
                c: {k: v for k, v in res.stats[c].items() if k in ("moments", "extrema")}
                for c in res.stats
            }))
            size = q.get("window", {}).get("size", 1)
            span = tuple(pos_of[i] for i in range(rec.pane_index - size + 1, rec.pane_index + 1)
                         if i in pos_of)
            ref = refs.get((q["name"], span))
            if ref is None:
                panes = [replay.host(pos) for pos in span]
                ref = refs[q["name"], span] = reference.WindowRef(
                    table, q, panes, config["edge_nodes"])
            checks = reference.merge_checks(checks, reference.check_result(host, ref))
    # slot k means one cell to both sides only if the tables agree
    checks["count_mismatches"] += float(
        not np.array_equal(np.asarray(sess.pipe.table.codes), table.codes)
    )
    checks["results_missing"] = float(missing)
    checks["steps_checked"] = float(len(sample))
    return checks
