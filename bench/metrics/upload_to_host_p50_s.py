"""Median of the program's ``upload.to_host`` spans: a miss's slot
state pulled from the device to the host before its first token."""
import statistics


def read(rec):
    xs = [s["dur"] for s in rec.spans if s["name"] == "upload.to_host"
          and rec.t0 <= s["t0"] < rec.t1]
    return statistics.median(xs) if xs else None
