"""The per-layer metrics read from the program's own spans, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness/test_span_metrics.py

The four readers on hand-made span lists (nearest rank, a parent less
its child, a union of overlapping waits that never passes 100%), and a
tiny gateway driven with the benchmark's probes installed: each program
span agrees with the probe around the same call.
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.lib import harness, probes  # noqa: E402

NEW = ("inbox_wait_p90_s", "upload_to_host_p50_s", "decode_host_p50_s",
       "engine_host_share")


def _span(name, t0, dur, span="", parent=""):
    return {"name": name, "t0": t0, "dur": dur, "span": span,
            "parent": parent, "trace": "", "proc": "gateway", "attrs": {}}


def _rec(spans, t0=0.0, t1=10.0):
    return harness.Records(t0=t0, t1=t1, spans=spans)


def test_every_new_metric_is_declared_with_a_reader():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in b["per_layer"]}
    for name in NEW:
        assert declared[name]["source"] == "program_span"
        assert "workloads" not in declared[name]
        assert callable(harness.load_reader(name))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_reads_nothing(name):
    """What a program that records none of these spans gives: the
    request's slot spans alone."""
    rec = _rec([_span("slot.prefill", 1.0, 0.5),
                _span("slot.decode", 1.5, 2.0)])
    assert harness.load_reader(name)(rec) is None


def test_inbox_wait_is_the_nearest_rank_p90_of_the_span():
    read = harness.load_reader("inbox_wait_p90_s")
    xs = [0.1 * i for i in range(1, 11)]           # 0.1 .. 1.0
    spans = [_span("gw.inbox_wait", 1.0 + i * 0.5, x)
             for i, x in enumerate(xs)]
    spans += [_span("gw.inbox_wait", 10.5, 99.0),  # after the span
              _span("gw.inbox_wait", -0.5, 99.0)]  # before it
    assert read(_rec(spans)) == pytest.approx(0.9)
    # nine values: rank ceil(8.1) = 9, the largest
    assert read(_rec(spans[:9])) == pytest.approx(0.9)
    assert read(_rec(spans[:1])) == pytest.approx(0.1)


def test_upload_to_host_is_the_median_in_the_span():
    read = harness.load_reader("upload_to_host_p50_s")
    spans = [_span("upload.to_host", 1.0, 0.4),
             _span("upload.to_host", 2.0, 0.5),
             _span("upload.to_host", 3.0, 0.3),
             _span("upload.encode", 3.5, 7.0),
             _span("upload.to_host", 11.0, 9.0)]
    assert read(_rec(spans)) == pytest.approx(0.4)


def test_decode_host_is_each_step_less_its_wait():
    read = harness.load_reader("decode_host_p50_s")
    spans = [_span("eng.decode", 1.0, 0.050, span="a"),
             _span("decode.wait", 1.001, 0.040, span="aw", parent="a"),
             _span("eng.decode", 2.0, 0.060, span="b"),
             _span("decode.wait", 2.001, 0.030, span="bw", parent="b"),
             _span("eng.decode", 3.0, 0.020, span="c"),   # no wait child
             # a wait under another parent is not this step's
             _span("decode.wait", 3.001, 0.015, span="x", parent="z"),
             _span("eng.sample", 3.002, 0.001, span="cs", parent="c")]
    # host parts: 0.010, 0.030, 0.020
    assert read(_rec(spans)) == pytest.approx(0.020)


def test_engine_host_share_unions_overlapping_waits():
    read = harness.load_reader("engine_host_share")
    spans = [_span("eng.step", 2.0, 1.0),
             # an idle stretch open before the span starts: clipped
             _span("eng.wait", -3.0, 4.0),            # 0 .. 1
             _span("decode.wait", 0.5, 1.5),          # 0.5 .. 2, overlaps
             _span("prefill.wait", 1.5, 1.0),         # 1.5 .. 2.5
             _span("eng.wait", 6.0, 2.0),             # 6 .. 8
             _span("decode.wait", 7.0, 0.5),          # inside the wait
             _span("eng.wait", 9.5, 5.0)]             # clipped at 10
    # waited: 0 .. 2.5, 6 .. 8, 9.5 .. 10 = 5.0 of 10 s
    assert read(_rec(spans)) == pytest.approx(50.0)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_engine_host_share_stays_within_0_and_100(seed):
    import numpy as np
    read = harness.load_reader("engine_host_share")
    rng = np.random.default_rng(seed)
    spans = [_span("eng.step", 0.0, 0.1)]
    for _ in range(200):
        spans.append(_span(str(rng.choice(["eng.wait", "prefill.wait",
                                           "decode.wait"])),
                           float(rng.uniform(-2, 11)),
                           float(rng.uniform(0, 3))))
    v = read(_rec(spans))
    assert 0.0 <= v <= 100.0
    assert read(_rec(spans[:1])) == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# the program's spans against the benchmark's probes, on a tiny gateway
# ---------------------------------------------------------------------------

def _median_pair_agrees(spans_s, probes_s):
    """The median pair differs by at most 5% of the probes' median or
    1 ms."""
    import statistics
    gap = statistics.median(a - b for a, b in zip(spans_s, probes_s))
    assert abs(gap) <= max(1e-3, 0.05 * statistics.median(probes_s)), \
        (gap, spans_s, probes_s)
    return True


def _within(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_program_spans_agree_with_the_probes(tiny_setup):
    """``gw.upload`` less the slot copy's dispatch, ``gw.resolve`` less
    its ``catalog.sync`` and ``eng.decode`` against
    ``bench.upload_extract``, ``bench.resolve`` and ``bench.decode_step``
    around the same calls: each program span pairs with exactly one
    probed call, and the median pair agrees within 5% or 1 ms. A single
    pair may not: the thread can be descheduled between a probe's edge
    and the span's."""
    import http.client
    from repro.core import Fabric
    from repro.gateway import Gateway
    from repro.serving.engine import BatchedEngine
    cfg, model, params = tiny_setup
    g = Gateway(model, params, fabric=Fabric.local(), batch_size=2,
                max_len=128).start()
    rec = probes.Recorder()
    try:
        def post(prompt):
            c = http.client.HTTPConnection("127.0.0.1", g.port, timeout=60)
            c.request("POST", "/v1/completions",
                      json.dumps({"prompt": prompt, "max_tokens": 6}),
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            body = json.loads(r.read())
            c.close()
            assert r.status == 200
            return body
        # warm first, as the benchmark does before it installs probes:
        # a first call's compiles are no part of the comparison
        post("a warm-up prompt, uploaded and decoded before the probes")
        g.engine.fetcher.flush_uploads()
        rec.install(probes.load_probe_files(ROOT / "bench" / "probes"))
        # the slot copy's dispatch is part of gw.upload and precedes
        # the probed call
        rec.wrap("slot_cache", BatchedEngine, "slot_cache")
        for i in range(5):
            post(f"agreement prompt number {i} with a few more words")
        g.engine.fetcher.flush_uploads()
        hit = post("agreement prompt number 0 with a few more words")
        assert hit["cache"]["matched_tokens"] > 0
        spans = g.tracer.spans()
    finally:
        g.stop()
        rec.uninstall()

    def iv(d):
        return (d["t0"], d["t0"] + d["dur"])

    def named(name):
        return [d for d in spans if d["name"] == name]

    def paired(calls, name, inner_is_span):
        out = []
        for c in calls:
            pc = (c[0], c[1])
            got = [d for d in named(name)
                   if (_within(iv(d), pc) if inner_is_span
                       else _within(pc, iv(d)))]
            assert len(got) == 1, (name, c)
            out.append((c[1] - c[0], got[0]))
        return out

    uploads = paired(rec.calls["bench.upload_extract"], "gw.upload", False)
    assert len(uploads) == 5
    copies = {d["span"]: c[1] - c[0] for c in rec.calls["slot_cache"]
              for _, d in uploads if _within(c, iv(d))}
    assert _median_pair_agrees([d["dur"] - copies[d["span"]]
                           for _, d in uploads],
                          [probe_s for probe_s, _ in uploads])

    syncs = {d["parent"]: d["dur"] for d in named("catalog.sync")}
    resolves = paired(rec.calls["bench.resolve"], "gw.resolve", False)
    assert len(resolves) == 6
    assert _median_pair_agrees([d["dur"] - syncs[d["span"]]
                           for _, d in resolves],
                          [probe_s for probe_s, _ in resolves])

    # a step whose rows all finished at their first token decodes nothing
    steps = [c for c in rec.calls["bench.decode_step"] if c[2]]
    decodes = paired(steps, "eng.decode", True)
    assert len(decodes) >= 5
    assert _median_pair_agrees([d["dur"] for _, d in decodes],
                          [probe_s for probe_s, _ in decodes])
