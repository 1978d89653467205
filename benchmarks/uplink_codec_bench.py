"""Uplink codec compression on the sparse-strata sketch workload.

The paper's bandwidth claim is that sufficient statistics beat tuples —
but the *dense* preagg frame still ships every sketch bin of every
stratum.  This bench builds the workload where that hurts most: a
Geohash-5 stratum table over the full Shenzhen bbox with the taxi fleet
confined to a small downtown sub-bbox (a handful of occupied strata out
of thousands), queried by a 4-column sketch query (p50/p99 over four
value columns — each column drags a full ``(S+1, 513)`` bin grid onto
the dense uplink).

Measured per codec: encoded frame bytes vs the analytic dense model
(:func:`repro.core.query.preagg_bytes`), the encode+decode round-trip
wall, and — for the lossless sparse codec — bit-exact estimate parity
against the dense uplink.  CI gates (``benchmarks/baselines.json``,
absolute):

  * ``uplink_codec_ratio`` >= 3.0 — the sparse codec must cut the
    sketch-heavy uplink by at least 3x (median of REPEATS re-measures);
  * ``codec_lossless_parity`` == 1 — every estimate field from the
    sparse-codec pipeline is bit-identical to the dense pipeline.

``--json PATH`` runs the fixed small CI configuration; the bare CSV mode
sweeps all codecs (sparse / delta / topk / quantize) across Geohash-5
and the ~32x-denser Geohash-6 table for the README's worked example.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (
    SHENZHEN_BBOX,
    AggSpec,
    EdgeCloudPipeline,
    PipelineConfig,
    Query,
    make_table,
    query as aqp,
    windows,
)
from repro.core import codec as wirecodec
from repro.data.streams import shenzhen_taxi_stream

from .common import REPEATS, csv_line, median_of_k

WINDOW = 20_000
FRACTION = 0.8
# the fleet stays downtown: a ~0.05 x 0.08 degree sub-bbox of Shenzhen,
# so a precision-5 table of the full city sees a handful of occupied strata
DOWNTOWN = ((22.53, 22.58), (114.05, 114.13))

CODECS = ("sparse", "delta", "topk16", "quantize16", "quantize8")

EXACT_FIELDS = ("value", "moe", "ci_low", "ci_high", "relative_error", "n", "population")


def _query() -> Query:
    """The 4-column sketch query: every column carries a bin grid."""
    return Query(
        aggs=(
            AggSpec("p50", "value"),
            AggSpec("p99", "value"),
            AggSpec("p50", "occupancy", name="p50_occ"),
            AggSpec("p99", "occupancy", name="p99_occ"),
            AggSpec("p50", "speed_sq", name="p50_sq"),
            AggSpec("p50", "wait", name="p50_wait"),
        )
    )


def _pane(window: int = WINDOW) -> dict:
    """One downtown pane with four value columns (two derived)."""
    w = next(
        windows.count_windows(
            shenzhen_taxi_stream(num_chunks=2, seed=0, bbox=DOWNTOWN), window
        )
    )
    value = np.asarray(w.value, np.float32)
    occ = np.asarray(w.extra["occupancy"], np.float32)
    return {
        "lat": jnp.asarray(w.lat, jnp.float32),
        "lon": jnp.asarray(w.lon, jnp.float32),
        "valid": jnp.asarray(w.valid),
        "value": jnp.asarray(value),
        "occupancy": jnp.asarray(occ),
        "speed_sq": jnp.asarray(value * value),
        "wait": jnp.asarray((1.0 - occ) * value),
    }


def _consolidated(pipe, win, key):
    """One dense execute: (plan, consolidated states, dense model bytes)."""
    q = _query()
    res = pipe.execute(q, key, win, fraction=FRACTION)
    plan = pipe.plan(q)
    return plan, res, aqp.preagg_bytes(plan, pipe.table.num_slots)


def _roundtrip_wall_us(codec_spec: str, stats) -> tuple[int, float, float]:
    """(encoded_bytes, encode_us, decode_us) for one frame (medians)."""
    codec = wirecodec.resolve_codec(codec_spec).for_stream()
    rows = wirecodec.flatten_stats(stats)
    enc_t, dec_t = [], []
    payload = codec.encode(rows)
    for _ in range(5):
        c = wirecodec.resolve_codec(codec_spec).for_stream()
        t0 = time.perf_counter()
        p = c.encode(rows)
        enc_t.append((time.perf_counter() - t0) * 1e6)
        t0 = time.perf_counter()
        c.decode(p)
        dec_t.append((time.perf_counter() - t0) * 1e6)
    return payload.nbytes, float(np.median(enc_t)), float(np.median(dec_t))


def run():
    key = jax.random.key(0)
    win = _pane()
    for precision in (5, 6):
        table = make_table(*SHENZHEN_BBOX, precision=precision)
        pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=WINDOW))
        _plan, res, dense = _consolidated(pipe, win, key)
        for spec in CODECS:
            nbytes, enc_us, dec_us = _roundtrip_wall_us(spec, res.stats)
            yield csv_line(
                f"uplink_codec_bench/{spec}_gh{precision}",
                enc_us + dec_us,
                f"window={WINDOW};strata={table.num_strata};dense={dense};"
                f"encoded={nbytes};ratio={dense / nbytes:.1f}x",
            )


def small_metrics(window: int = WINDOW, fraction: float = FRACTION) -> dict:
    """Fixed small-configuration metrics for CI regression tracking.

    The two acceptance gates of the uplink codec layer (absolute, see
    ``benchmarks/baselines.json``): a >= 3x sparse-codec byte reduction on
    the sparse-strata sketch workload, and bit-exact estimate parity
    between the sparse-codec and dense pipelines.
    """
    key = jax.random.key(0)
    win = _pane(window)
    table = make_table(*SHENZHEN_BBOX, precision=5)
    pipe = EdgeCloudPipeline(table, PipelineConfig(raw_capacity=window))
    _plan, res, dense = _consolidated(pipe, win, key)

    def measured_ratio() -> float:
        nbytes, _enc, _dec = _roundtrip_wall_us("sparse", res.stats)
        return dense / nbytes

    ratio = median_of_k(measured_ratio, REPEATS)
    nbytes, enc_us, dec_us = _roundtrip_wall_us("sparse", res.stats)

    # parity: the sparse-codec pipeline's estimates must be bit-identical
    pipe_c = EdgeCloudPipeline(
        table, PipelineConfig(raw_capacity=window, uplink_codec="sparse")
    )
    res_c = pipe_c.execute(_query(), key, win, fraction=fraction)
    parity = 1
    for k in res.estimates:
        for field in EXACT_FIELDS:
            a = np.asarray(getattr(res.estimates[k], field))
            b = np.asarray(getattr(res_c.estimates[k], field))
            if not np.array_equal(a, b, equal_nan=True):
                parity = 0
    topk_bytes, _, _ = _roundtrip_wall_us("topk16", res.stats)
    q8_bytes, _, _ = _roundtrip_wall_us("quantize8", res.stats)

    return {
        "config": {
            "window": window,
            "fraction": fraction,
            "precision": 5,
            "strata": int(table.num_strata),
            "columns": 4,
            "sub_bbox": "downtown",
        },
        "repeats": REPEATS,
        "dense_bytes": int(dense),
        "encoded_bytes": int(nbytes),
        "uplink_codec_ratio": ratio,
        "codec_lossless_parity": parity,
        "codec_encode_us": enc_us,
        "codec_decode_us": dec_us,
        "topk16_ratio": dense / topk_bytes,
        "quantize8_ratio": dense / q8_bytes,
    }


def main() -> None:
    """Standalone entry: ``python -m benchmarks.uplink_codec_bench
    [--json PATH]``."""
    import sys

    from .common import json_flag_path, write_metrics_json

    path = json_flag_path(sys.argv[1:])
    if path is not None:
        write_metrics_json(path, small_metrics(), "uplink_codec_bench")
        return
    print("name,us_per_call,derived")
    for line in run():
        print(line, flush=True)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
