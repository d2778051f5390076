"""Share of the traced span in which the engine thread neither waited
for work (``eng.wait``) nor for a device program (``prefill.wait``,
``decode.wait``): 100 x (span - union of those spans, clipped to it) /
span (%). None when the program records no engine spans there."""
WAITS = ("eng.wait", "prefill.wait", "decode.wait")
ENGINE = ("eng.step", "eng.wait")


def read(rec):
    lo, hi = rec.t0, rec.t1
    live = [s for s in rec.spans
            if s["t0"] < hi and s["t0"] + s["dur"] > lo]
    if hi <= lo or not any(s["name"] in ENGINE for s in live):
        return None
    iv = sorted((max(s["t0"], lo), min(s["t0"] + s["dur"], hi))
                for s in live if s["name"] in WAITS)
    waited, end = 0.0, lo
    for a, b in iv:
        a = max(a, end)
        if b > a:
            waited += b - a
            end = b
    return 100.0 * (hi - lo - waited) / (hi - lo)
