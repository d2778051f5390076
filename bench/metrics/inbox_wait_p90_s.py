"""90th percentile (nearest rank) of the program's ``gw.inbox_wait``
spans: a request handed to the engine thread waiting for it to pick the
request up, of requests picked up in the traced span."""
from bench.lib.stats import nearest_rank


def read(rec):
    xs = [s["dur"] for s in rec.spans if s["name"] == "gw.inbox_wait"
          and rec.t0 <= s["t0"] < rec.t1]
    return nearest_rank(xs, 90) if xs else None
