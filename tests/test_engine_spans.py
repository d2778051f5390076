"""Engine spans on the profiler's clock: every span the serving loop
stores is also a ``jax.profiler`` host event of the same name, the same
length, and a start that maps onto the stored one by one offset shared
by all spans."""
import glob
import os
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.obs.trace import Tracer
from repro.serving.engine import BatchedEngine
from repro.serving.scheduler import Request, Scheduler

LIVE = {"eng.step", "eng.prefill", "prefill.wait", "prefill.scatter",
        "eng.decode", "decode.wait", "eng.sample"}
TOL_S = 1e-3


def _requests(n, vocab, max_new=4):
    rng = np.random.default_rng(7)
    return [Request(tokens=rng.integers(3, vocab, 6 + 3 * i,
                                        dtype=np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def _host_events(logdir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in LIVE:
                        out[e.name].append((e.start_ns * 1e-9,
                                            e.duration_ns * 1e-9))
    return out


def test_stored_spans_map_onto_the_profiler_host_plane(tiny_setup,
                                                       tmp_path):
    cfg, model, params = tiny_setup
    eng = BatchedEngine(model, params, max_len=64, batch_size=2)
    Scheduler(eng).run(_requests(3, cfg.vocab))      # compile outside
    tr = Tracer(proc="t")
    sched = Scheduler(eng, tracer=tr)
    for r in _requests(3, cfg.vocab):
        sched.submit(r)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        step = 0
        while sched.has_work:
            with tr.engine("eng.step", step_num=step):
                sched.step()
            step += 1
    finally:
        jax.profiler.stop_trace()
    stored = defaultdict(list)
    for d in tr.trace("engine"):
        stored[d["name"]].append((d["t0"], d["dur"]))
    assert set(stored) == LIVE
    events = _host_events(str(tmp_path))
    pairs = []
    for name in LIVE:
        mine, theirs = sorted(stored[name]), sorted(events[name])
        assert len(mine) == len(theirs), name
        pairs += [(name, a, b) for a, b in zip(mine, theirs)]
    offset = float(np.median([b[0] - a[0] for _, a, b in pairs]))
    for name, (t0, dur), (s, d) in pairs:
        assert abs(d - dur) < TOL_S, (name, dur, d)
        assert abs(s - t0 - offset) < TOL_S, (name, t0, s, offset)


@pytest.mark.parametrize("steps", [1, 5])
def test_step_spans_carry_their_number_and_rows(tiny_setup, steps):
    cfg, model, params = tiny_setup
    eng = BatchedEngine(model, params, max_len=64, batch_size=2)
    tr = Tracer(proc="t")
    sched = Scheduler(eng, tracer=tr)
    for r in _requests(2, cfg.vocab, max_new=8):
        sched.submit(r)
    for i in range(steps):
        with tr.engine("eng.step", step_num=i):
            sched.step()
    ring = tr.trace("engine")
    assert [d["attrs"]["step_num"] for d in ring
            if d["name"] == "eng.step"] == list(range(steps))
    decodes = [d for d in ring if d["name"] == "eng.decode"]
    assert len(decodes) == steps
    assert decodes[0]["attrs"]["rows"] == 2
    # live lengths: each prompt plus the token this step writes
    assert decodes[0]["attrs"]["live"] == [7, 10]
    prefill = [d for d in ring if d["name"] == "eng.prefill"]
    assert len(prefill) == 1 and prefill[0]["attrs"]["rows"] == 2
    wait = [d for d in ring if d["name"] == "prefill.wait"]
    assert wait[0]["attrs"]["bucket"] == 16
    assert wait[0]["parent"] == prefill[0]["span"]
