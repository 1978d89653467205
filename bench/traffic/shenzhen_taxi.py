"""The Shenzhen e-taxi stream, as the benchmark generates it from a seed.

A copy of ``repro.data.streams.shenzhen_taxi_stream``, kept here so that a
change to the program cannot change the data the benchmark measures it on.
A test checks that the two still agree for a seed.

Mobility: ``num_vehicles`` taxis random-walk inside the bounding box, each
pulled toward one of five "downtown" attractors (70% of the fleet in the top
two); speed is low near the attractors and high in the outskirts, occupancy
the other way round.  Chunks of ``chunk_size`` tuples cover one event-minute
each, with timestamps sorted within the minute.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np


def _attractors(rng, bbox, k):
    (lat_lo, lat_hi), (lon_lo, lon_hi) = bbox
    lats = rng.uniform(lat_lo + 0.1 * (lat_hi - lat_lo), lat_hi - 0.1 * (lat_hi - lat_lo), k)
    lons = rng.uniform(lon_lo + 0.1 * (lon_hi - lon_lo), lon_hi - 0.1 * (lon_hi - lon_lo), k)
    return np.stack([lats, lons], axis=1)


def stream(
    seed: int,
    bbox,
    num_vehicles: int = 664,
    chunk_size: int = 20_000,
    num_chunks: int = 60,
) -> Iterator[dict]:
    """Chunks of (sensor_id, timestamp, lat, lon, value=speed, occupancy)."""
    rng = np.random.default_rng(seed)
    (lat_lo, lat_hi), (lon_lo, lon_hi) = bbox
    centers = _attractors(rng, bbox, 5)
    home = rng.choice(len(centers), num_vehicles, p=[0.45, 0.25, 0.15, 0.10, 0.05])
    pos = centers[home] + rng.normal(0, 0.02, (num_vehicles, 2))
    t = 0.0
    for _ in range(num_chunks):
        ids = rng.integers(0, num_vehicles, chunk_size)
        step = rng.normal(0, 0.004, (chunk_size, 2))
        pull = (centers[home[ids]] - pos[ids]) * 0.05
        pos_ids = pos[ids] + step + pull
        pos_ids[:, 0] = np.clip(pos_ids[:, 0], lat_lo, lat_hi)
        pos_ids[:, 1] = np.clip(pos_ids[:, 1], lon_lo, lon_hi)
        pos[ids] = pos_ids
        d = np.min(np.linalg.norm(pos_ids[:, None, :] - centers[None, :, :], axis=-1), axis=1)
        speed = 12.0 + 55.0 * np.tanh(d / 0.08) + rng.normal(0, 4.0, chunk_size)
        speed = np.clip(speed, 0.0, 120.0)
        occupancy = np.clip(
            0.85 - 0.6 * np.tanh(d / 0.08) + rng.normal(0, 0.08, chunk_size), 0.0, 1.0
        )
        ts = t + np.sort(rng.uniform(0, 60.0, chunk_size))
        t += 60.0
        yield dict(
            sensor_id=ids.astype(np.int32),
            timestamp=ts,
            lat=pos_ids[:, 0].astype(np.float32),
            lon=pos_ids[:, 1].astype(np.float32),
            value=speed.astype(np.float32),
            occupancy=occupancy.astype(np.float32),
        )
