"""Data substrate: synthetic geo-referenced streams and paced/bursty sources."""

from .sources import BurstySource, PacedSource
from .streams import chicago_aq_stream, shenzhen_taxi_stream

__all__ = [
    "BurstySource",
    "PacedSource",
    "chicago_aq_stream",
    "shenzhen_taxi_stream",
]
