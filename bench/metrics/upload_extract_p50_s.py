"""Median host time of ``bench.upload_extract`` (a miss's state pulled
to the host and compressed before its first token) in the window."""
import statistics


def read(rec):
    calls = rec.calls("bench.upload_extract")
    return statistics.median(c[1] - c[0] for c in calls) if calls else None
