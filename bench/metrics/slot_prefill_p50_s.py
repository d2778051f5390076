"""Median of the program's ``slot.prefill`` spans (admission to first
token) of requests admitted in the window."""
import statistics


def read(rec):
    xs = [s["dur"] for s in rec.spans if s["name"] == "slot.prefill"
          and rec.t0 <= s["t0"] < rec.t1]
    return statistics.median(xs) if xs else None
