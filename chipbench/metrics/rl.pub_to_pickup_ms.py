"""Median time from a version's publication to the engine's pickup, over
the versions published in the window (``AsyncScheduler.pickup_latencies``)."""
import statistics


def read(ctx):
    lat = ctx["counters"]["pickup_ms"]
    return statistics.median(lat) if lat else None
