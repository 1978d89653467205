"""The finalize program assembles a multi-pane window.

The session hands a sliding window's pane ring to the finalize program as
it is (one stats pytree and four counters a pane); the program stacks the
panes, merges them and sums the counters.  For rings of 2, 3 and 4 panes at
Geohash-5 and -6, on the single and the batched finalize:

  * counts, extrema and the ``n_*`` counters equal a NumPy recomputation
    over the same panes;
  * every estimate and merged state is bit-equal to the eager path it
    replaces: the ring stacked leaf by leaf on the host, then a jitted
    ``merge_accs_panes`` + ``finalize`` over the stacked stats (vmapped
    over the members on the batched path);
  * on a runtime run, ``session.program_stacked_windows`` counts each
    ``session.stack`` hand-off, and nothing compiles under it.
"""

import functools

import numpy as np
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
    estimators,
    make_table,
    windows,
)
from repro.core import query as aqp
from repro.data.streams import shenzhen_taxi_stream

PANE = 400
QUERY = Query(
    aggs=(
        AggSpec("sum", "value"),
        AggSpec("count", "value"),
        AggSpec("min", "value"),
        AggSpec("max", "value"),
        AggSpec("var", "value"),
        AggSpec("p50", "value"),
    )
)
# one member: the single finalize; two at divergent fractions (a refined
# pass, so their samples differ): one batched finalize for both
FRACTIONS = {"single": (1.0,), "batched": (0.9, 0.4)}
COUNTED = {"moments": ("n", "total"), "sketch": ("bins",)}


@functools.cache
def _pipe(precision: int) -> EdgeCloudPipeline:
    return EdgeCloudPipeline(make_table(*SHENZHEN_BBOX, precision=precision), PipelineConfig())


@pytest.fixture(scope="module")
def panes():
    stream = shenzhen_taxi_stream(chunk_size=PANE, num_chunks=5, seed=3)
    return list(windows.pane_windows(stream, pane_tuples=PANE))


def _eager_windows(pipe, regs, key) -> list:
    """The emit before the ring went into the program: each ring stacked
    leaf by leaf on the host, then the jitted merge and finalize of the
    stack; vmapped over the members, as the batched program was, when there
    are several."""
    plan = regs[0].plan
    rings = [
        jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *[p.stats for p in reg.ring])
        for reg in regs
    ]

    def body(stacked, bkey):
        merged = {c: estimators.merge_accs_panes(stacked[c]) for c in plan.columns}
        return aqp.finalize(plan, pipe.table, merged, key=bkey), merged

    if len(regs) == 1:
        return [jax.jit(body)(rings[0], key)]

    @jax.jit
    def finalize_batched(rings, bkey):
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *rings)
        return jax.vmap(body, in_axes=(0, None))(stacked, bkey)

    out = finalize_batched(rings, key)
    return [jax.tree.map(lambda x: x[i], out) for i in range(len(regs))]


@pytest.mark.parametrize("path", sorted(FRACTIONS))
@pytest.mark.parametrize("num_panes", [2, 3, 4])
@pytest.mark.parametrize("precision", [5, 6])
def test_finalize_program_assembles_the_window(precision, num_panes, path, panes):
    pipe = _pipe(precision)
    sess = StreamSession(pipe)
    window = WindowSpec("sliding", size=num_panes)
    regs = [sess.register(QUERY, window=window, initial_fraction=f) for f in FRACTIONS[path]]
    rt = StreamRuntime(sess, key=jax.random.key(precision), config=RuntimeConfig(policy="block"))
    rt.run(panes[: num_panes + 1])  # the ring fills, then rolls once
    st = rt.stats()
    handoffs = st.spans["session.stack"]["count"]
    assert handoffs == len(regs) * num_panes  # every multi-pane ring, per member
    assert st.counters["session.program_stacked_windows"] == handoffs
    assert st.counters["session.finalize_dispatches"] == num_panes + 1  # one a pane
    assert "compiles.session.stack" not in st.counters

    key = jax.random.key(11)
    out = sess.emit_all(key)
    for reg, want in zip(regs, _eager_windows(pipe, regs, key)):
        res, ring = out[reg.qid], reg.ring
        assert len(ring) == num_panes
        for f in ("n_sampled", "n_valid", "n_overflow", "n_truncated"):
            assert int(getattr(res, f)) == sum(int(getattr(p, f)) for p in ring), f
        host = [jax.device_get(p.stats) for p in ring]
        for c in reg.plan.columns:
            merged = res.stats[c]
            for kind, fields in COUNTED.items():
                for f in fields:
                    panes_f = np.stack([getattr(h[c][kind], f) for h in host]).astype(np.float64)
                    np.testing.assert_array_equal(
                        np.asarray(getattr(merged[kind], f), np.float64), panes_f.sum(axis=0)
                    )
            lo = np.stack([h[c]["extrema"].min for h in host]).min(axis=0)
            hi = np.stack([h[c]["extrema"].max for h in host]).max(axis=0)
            np.testing.assert_array_equal(np.asarray(merged["extrema"].min), lo)
            np.testing.assert_array_equal(np.asarray(merged["extrema"].max), hi)
        got = (res.estimates, res.stats)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
