"""Reduction of a profiler trace to device busy time, idle gaps and
per-program device time.

Input is plain tuples so the reduction can be tested without a chip:
device ops and programs as ``(name, start_ns, dur_ns)`` per device, and
host annotations as ``(name, start_ns, dur_ns)``, all on the trace's
own clock. :func:`read_xplane` builds them from a ``.xplane.pb``.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, dur_ns


def short(name: str) -> str:
    """An XLA op event is named by its whole HLO line; keep the op's
    own name (``%fusion.12 = bf16[...] ...`` -> ``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str, prefix: str = "bench.") -> dict:
    """{"ops": {device: [Event]}, "programs": {device: [Event]},
    "host": [Event]} from a profiler trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = defaultdict(list)
    progs: Dict[str, List[Event]] = defaultdict(list)
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name].extend(
                        (short(e.name), e.start_ns, e.duration_ns)
                        for e in line.events)
                elif line.name == "XLA Modules":
                    progs[plane.name].extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(prefix))
    return {"ops": dict(ops), "programs": dict(progs), "host": host}


def merged(events: Sequence[Event], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    """Union of the events' intervals, clipped to [lo, hi)."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                if s < hi and s + d > lo)
    out: List[List[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out if b > a]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(events, lo, hi))


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi) in which no event runs."""
    out, t = [], lo
    for a, b in merged(events, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap: Tuple[float, float], host: Sequence[Event],
              skip: Sequence[str] = ("bench.window",)) -> str:
    """What the host was doing in the gap: at each instant the innermost
    (shortest) annotation open then; the name that holds the most of the
    gap wins. 'unattributed' when no annotation overlaps it."""
    a, b = gap
    live = [(s, s + d, name) for name, s, d in host
            if name not in skip and s < b and s + d > a]
    if not live:
        return "unattributed"
    cuts = sorted({a, b} | {t for s, e, _ in live for t in (s, e)
                            if a < t < b})
    held: Dict[str, float] = defaultdict(float)
    for lo, hi in zip(cuts, cuts[1:]):
        inner = [(e - s, name) for s, e, name in live
                 if s <= lo and e >= hi]
        if inner:
            held[min(inner)[1]] += hi - lo
    return max(held.items(), key=lambda kv: kv[1])[0] if held \
        else "unattributed"


def window_of(host: Sequence[Event], name: str = "bench.window"
              ) -> Tuple[float, float]:
    w = [e for e in host if e[0] == name]
    if not w:
        raise ValueError(f"no {name} annotation in the trace")
    _, s, d = max(w, key=lambda e: e[2])
    return s, s + d


def reduce(tr: dict, top: int = 10) -> dict:
    """busy_s (mean over devices), window_s, per-program device
    seconds, and the breakdown's top device ops and longest idle gaps
    (named by what the host was doing)."""
    lo, hi = window_of(tr["host"])
    devs = sorted(tr["ops"])
    if not devs:
        raise ValueError("the trace holds no device operations")
    busy = [busy_ns(tr["ops"][d], lo, hi) for d in devs]
    per_op: Dict[str, float] = defaultdict(float)
    for d in devs:
        for name, s, du in tr["ops"][d]:
            if lo <= s < hi:
                per_op[name] += du
    per_prog: Dict[str, List[float]] = defaultdict(list)
    for d in devs:
        for name, s, du in tr["programs"].get(d, []):
            if lo <= s < hi:
                per_prog[name].append(du * 1e-9)
    g = sorted(gaps(tr["ops"][devs[0]], lo, hi), key=lambda x: x[0] - x[1])
    idle = [[attribute(x, tr["host"]), (x[1] - x[0]) * 1e-9]
            for x in g[:top]]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "programs": dict(per_prog),
            "breakdown": {"device_ops": [[n, v * 1e-9] for n, v in ops],
                          "idle_gaps": idle}}
