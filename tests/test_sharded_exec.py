"""Multi-device integration (subprocess, 8 host devices): the routed
all_to_all exchange and the sharded quantile query agree with references."""

import os
import subprocess
import sys

import pytest

# each test spawns a full 8-device jax subprocess; serialize them onto one
# xdist worker so parallel shards don't oversubscribe the CPU
pytestmark = pytest.mark.xdist_group("subprocess-heavy")


def _run(code: str, timeout=900):
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=timeout,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-2000:])
    return r.stdout


def test_routed_exchange_delivers_to_owner_shards():
    out = _run(
        """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import make_table, contiguous_plan, SHENZHEN_BBOX
from repro.core.routing import exchange
from repro.sharding.compat import compat_make_mesh

mesh = compat_make_mesh((8,), ("data",))
table = make_table(*SHENZHEN_BBOX, precision=5, neighborhood_precision=3)
plan = contiguous_plan(table, num_shards=8)
rng = np.random.default_rng(0)
N = 8 * 512
sidx = jnp.asarray(rng.integers(0, table.num_strata, N), jnp.int32)
payload = jnp.asarray(rng.normal(0, 1, N), jnp.float32)

def shard_fn(s, p):
    valid, rx_s, rx_p, dropped = exchange(plan, s, p, "data", capacity=256)
    return valid, rx_s, rx_p, dropped[None]

mapped = jax.jit(jax.shard_map(shard_fn, mesh=mesh,
    in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data"), P("data"), P("data")),
    check_vma=False))
valid, rx_s, rx_p, dropped = mapped(sidx, payload)
valid, rx_s = np.asarray(valid), np.asarray(rx_s)
dest_of = np.asarray(plan.dest_of_stratum)
# every received tuple on shard d must be destined for d
per_shard = rx_s.reshape(8, -1)
per_valid = valid.reshape(8, -1)
for d in range(8):
    got = per_shard[d][per_valid[d]]
    assert (dest_of[got] == d).all(), d
# conservation: valid received == sent (minus drops)
sent = N - int(np.asarray(dropped).sum())
assert per_valid.sum() == sent
print("EXCHANGE_OK", per_valid.sum(), int(np.asarray(dropped).sum()))
"""
    )
    assert "EXCHANGE_OK" in out


def test_sharded_quantiles_and_backend_parity():
    """execute_sharded answers p50/p99 end-to-end over 8 host-mesh edge
    shards (sketch psum across the uplink), and the fused edge-reduce
    backend matches the per-column segment backend shard-for-shard."""
    out = _run(
        """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import (
    SHENZHEN_BBOX, AggSpec, EdgeCloudPipeline, PipelineConfig, Query,
    make_table, windows,
)
from repro.data.streams import shenzhen_taxi_stream
from repro.sharding.compat import compat_make_mesh

mesh = compat_make_mesh((8,), ("data",))
table = make_table(*SHENZHEN_BBOX, precision=5)
window = next(windows.count_windows(shenzhen_taxi_stream(num_chunks=2, seed=0), 32_768))
q = Query(aggs=(AggSpec("mean", "value"), AggSpec("p50", "value"), AggSpec("p99", "value")))
res = {}
for backend in ("segment", "pallas"):
    pipe = EdgeCloudPipeline(table, PipelineConfig(backend=backend), mesh=mesh)
    res[backend] = pipe.execute_sharded(q, jax.random.key(1), window, fraction=1.0)

# full fraction: the merged sketch must hit the exact numpy quantiles
sidx = np.asarray(table.assign(jnp.asarray(window.lat), jnp.asarray(window.lon)))
v = window.value[sidx < table.num_strata]
for key, quant in (("p50_value", 0.5), ("p99_value", 0.99)):
    got = float(res["segment"].estimates[key].value)
    true = float(np.quantile(v, quant))
    assert abs(got - true) <= 0.05 * abs(true) + 1e-3, (key, got, true)

# backend parity on the same shard split: sketch bins identical, moments
# within the documented fp32 centering tolerance
for key in ("mean_value", "p50_value", "p99_value"):
    a = float(res["segment"].estimates[key].value)
    b = float(res["pallas"].estimates[key].value)
    assert abs(a - b) <= 1e-4 * max(1.0, abs(a)), (key, a, b)
assert int(res["segment"].n_sampled) == int(res["pallas"].n_sampled)
bins_a = np.asarray(res["segment"].stats["value"]["sketch"].bins)
bins_b = np.asarray(res["pallas"].stats["value"]["sketch"].bins)
np.testing.assert_array_equal(bins_a, bins_b)
print("SHARDED_QUANTILE_OK", float(res["segment"].estimates["p99_value"].value))
"""
    )
    assert "SHARDED_QUANTILE_OK" in out
