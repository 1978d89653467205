"""Stream generators: the chunk schema and the spatial skew of the mobility stream."""

import numpy as np

import jax.numpy as jnp

from repro.data.streams import chicago_aq_stream, materialize, shenzhen_taxi_stream


def test_stream_generators_schema():
    for gen in (shenzhen_taxi_stream(num_chunks=2), chicago_aq_stream(num_chunks=2)):
        chunk = next(iter(gen))
        n = len(chunk["lat"])
        assert n > 0
        for k in ("sensor_id", "timestamp", "lat", "lon", "value"):
            assert len(chunk[k]) == n
        assert np.all(np.diff(chunk["timestamp"]) >= 0)


def test_streams_spatially_skewed():
    data = materialize(shenzhen_taxi_stream(num_chunks=4, seed=0))
    from repro.core import SHENZHEN_BBOX, make_table

    t = make_table(*SHENZHEN_BBOX, precision=5)
    sidx = np.asarray(t.assign(jnp.asarray(data["lat"]), jnp.asarray(data["lon"])))
    counts = np.bincount(sidx, minlength=t.num_slots)[:-1]
    nz = counts[counts > 0]
    # heavy skew: the top decile of occupied cells holds a large share
    top = np.sort(nz)[-max(1, len(nz) // 10):].sum()
    assert top / nz.sum() > 0.3
    # and the median cell is far below the mean (long tail)
    assert np.median(nz) < 0.5 * nz.mean()
