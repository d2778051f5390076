"""Median over the program's ``eng.decode`` spans of the span's time
less that of its ``decode.wait`` child (the wait for the decode program
and its logits): the host work serial with each token."""
import statistics


def read(rec):
    steps = {s["span"]: s for s in rec.spans if s["name"] == "eng.decode"
             and rec.t0 <= s["t0"] < rec.t1}
    waits = {}
    for s in rec.spans:
        if s["name"] == "decode.wait" and s["parent"] in steps:
            waits[s["parent"]] = waits.get(s["parent"], 0.0) + s["dur"]
    xs = [s["dur"] - waits.get(k, 0.0) for k, s in steps.items()]
    return statistics.median(xs) if xs else None
