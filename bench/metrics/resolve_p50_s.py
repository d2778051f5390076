"""Median host time of ``bench.resolve`` (fetch, parse and restore of a
cached prefix, on the engine thread) over calls in the window."""
import statistics


def read(rec):
    calls = rec.calls("bench.resolve")
    return statistics.median(c[1] - c[0] for c in calls) if calls else None
