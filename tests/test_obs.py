"""Observability: span trees (nesting, cross-thread handoff, remote
folding), the engine ring, the host-annotation hook, Prometheus
exposition, Perfetto export, trace-context wire interop (v2 <-> v3),
the failure flight recorder, and the JAX-free import graph of
``repro.obs`` + the peer daemon."""
import gc
import json
import os
import subprocess
import sys
import threading

import pytest

from repro.config import CacheConfig
from repro.core import (CacheServer, EdgeClient, SimClock, SimNetwork,
                        state_io)
from repro.core.metrics import Breakdown
from repro.core.transport import InProcTransport
from repro.data import MMLUGenerator, WordHashTokenizer
from repro.obs import clock as oclock
from repro.obs.export import perfetto_trace, span_tree, write_perfetto
from repro.obs.flight import CHUNK_ERROR, FLIGHT, FlightRecorder
from repro.obs.metrics import MetricsRegistry, merge_snapshots
from repro.obs import trace as otrace
from repro.obs.trace import (ENGINE_TRACE, NULL_SPAN, NULL_TRACER,
                             SPANS_KEY, TRACE_KEY, SpanContext, Tracer,
                             current_span, extract_trace, inject_trace,
                             phase)
from repro.serving.engine import InferenceEngine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# spans: nesting, ambient parents, cross-thread handoff
# ---------------------------------------------------------------------------

def test_span_nesting_via_ambient_parent():
    tr = Tracer(proc="t")
    with tr.start("root") as root:
        with tr.start("child") as child:
            with phase("grandchild", k=1) as gc:
                assert gc.parent_id == child.span_id
        assert child.parent_id == root.span_id
    spans = tr.trace(root.trace_id)
    assert {d["name"] for d in spans} == {"root", "child", "grandchild"}
    assert all(d["trace"] == root.trace_id for d in spans)
    tree = span_tree(spans)
    assert tree["name"] == "root"
    assert tree["children"][0]["name"] == "child"
    assert tree["children"][0]["children"][0]["name"] == "grandchild"


def test_cross_thread_handoff_is_explicit():
    tr = Tracer(proc="t")
    got = {}

    def worker(ctx):
        # nothing leaks through thread ancestry ...
        assert current_span() is None
        # ... until the worker attaches the handed-over context
        with tr.attach(ctx):
            with phase("worker.step") as sp:
                got["parent"] = sp.parent_id
                got["trace"] = sp.trace_id

    with tr.start("root") as root:
        t = threading.Thread(target=worker, args=(root.ctx,))
        t.start()
        t.join()
    assert got["parent"] == root.span_id
    assert got["trace"] == root.trace_id


def test_null_tracer_and_disabled_paths_are_inert():
    sp = NULL_TRACER.start("x")
    assert sp is NULL_SPAN and not sp
    with sp:
        with phase("y") as p:
            assert p is NULL_SPAN
    assert NULL_TRACER.spans() == []


def test_tracer_alias_and_bounded_store():
    tr = Tracer(proc="t", max_traces=2)
    ids = []
    for i in range(3):
        with tr.start(f"r{i}") as sp:
            pass
        ids.append(sp.trace_id)
        tr.alias(f"cmpl-{i}", sp.trace_id)
    assert tr.trace(ids[0]) is None          # FIFO-evicted
    assert tr.trace("cmpl-0") is None        # alias evicted with it
    assert tr.trace("cmpl-2")[0]["name"] == "r2"


def test_engine_ring_is_bounded_keeps_newest_and_spares_requests(
        monkeypatch):
    monkeypatch.setattr(otrace, "ENGINE_SPANS", 6)
    tr = Tracer(proc="t", max_traces=2)
    with tr.start("gw.request") as req:
        pass
    for i in range(10):
        with tr.engine("eng.step", step_num=i) as step:
            with tr.engine("eng.decode"):
                with phase("decode.wait") as w:
                    assert w.trace_id == ENGINE_TRACE
        assert step.parent_id == ""          # a root of the ring
    ring = tr.trace("engine")
    assert len(ring) == 6                    # bounded
    steps = [d for d in ring if d["name"] == "eng.step"]
    assert [d["attrs"]["step_num"] for d in steps] == [8, 9]   # newest
    decode = {d["span"]: d for d in ring if d["name"] == "eng.decode"}
    assert all(d["parent"] in decode for d in ring
               if d["name"] == "decode.wait")
    # thirty engine roots evicted no request trace
    assert tr.trace(req.trace_id)[0]["name"] == "gw.request"
    assert tr.trace_ids() == [req.trace_id]
    assert tr.spans() == tr.trace(req.trace_id) + ring


def test_engine_span_inside_a_request_span_roots_in_the_ring():
    tr = Tracer(proc="t")
    with tr.start("gw.request") as req:
        with tr.engine("eng.wait") as w:
            pass
    assert w.trace_id == ENGINE_TRACE and w.parent_id == ""
    assert [d["name"] for d in tr.trace(req.trace_id)] == ["gw.request"]
    assert Tracer(enabled=False).engine("eng.step") is NULL_SPAN


def test_annotation_hook_wraps_entered_spans_only():
    seen = []

    class Ann:
        def __init__(self, name, attrs):
            self.name, self.attrs = name, dict(attrs)

        def __enter__(self):
            seen.append(("enter", self.name, self.attrs))

        def __exit__(self, *exc):
            seen.append(("exit", self.name, self.attrs))

    was = otrace._annotate
    otrace.set_annotation(Ann)
    try:
        tr = Tracer(proc="t")
        with tr.engine("eng.step", step_num=3):
            with phase("decode.wait"):
                pass
            tr.add("after.the.fact", 0.001)
        NULL_TRACER.add("x", 0.1)
        with NULL_TRACER.start("y"):
            pass
    finally:
        otrace.set_annotation(was)
    assert seen == [("enter", "eng.step", {"step_num": 3}),
                    ("enter", "decode.wait", {}),
                    ("exit", "decode.wait", {}),
                    ("exit", "eng.step", {"step_num": 3})]


def test_gc_collections_become_engine_spans(monkeypatch):
    monkeypatch.setattr(otrace, "GC_MIN_S", 0.0)
    tr = Tracer(proc="t")
    otrace.watch_gc(tr)
    try:
        gc.collect()
    finally:
        otrace.unwatch_gc(tr)
    assert otrace._on_gc not in gc.callbacks
    spans = [d for d in tr.trace("engine") if d["name"] == "py.gc"]
    assert spans and spans[-1]["attrs"]["generation"] == 2
    assert spans[-1]["attrs"]["thread"] == threading.current_thread().name
    assert spans[-1]["parent"] == "" and spans[-1]["dur"] >= 0.0


def test_fold_remote_centers_server_window():
    tr = Tracer(proc="client")
    net = tr.start("net.get", t0=100.0)
    net.end(t1=100.4)                        # 400 ms round trip
    n = tr.fold_remote(net, [
        {"name": "peer.get", "rel_s": 0.0, "dur_s": 0.2,
         "attrs": {"pid": 42}},
        {"name": "chunk.verify", "rel_s": 0.05, "dur_s": 0.1,
         "attrs": {}},
    ], proc="peer:p0")
    assert n == 2
    spans = {d["name"]: d for d in tr.trace(net.trace_id)}
    folded = spans["peer.get"]
    assert folded["parent"] == net.span_id
    assert folded["proc"] == "peer:p0"
    assert folded["attrs"]["remote"] is True
    assert folded["attrs"]["pid"] == 42
    # 0.2 s server window centered in the 0.4 s client span
    assert folded["t0"] == pytest.approx(100.1)
    assert folded["t0"] + folded["dur"] <= net.t0 + net.dur + 1e-9


def test_breakdown_is_projection_of_span_tree():
    tr = Tracer(proc="client")
    root = tr.start("infer")
    with root:
        tr.add("bloom", 0.01, component="bloom")
        # the attempt span covers transfer+restore; only the
        # transfer-visible time is the Table-3 redis column
        tr.add("redis.attempt", 0.30, component="redis",
               component_s=0.25)
        tr.add("p_decode", 0.50, component="p_decode")
        tr.add("r_decode", 0.40, component="r_decode")
        tr.add("untagged.phase", 9.9)        # no component: not summed
    wall = Breakdown.from_spans(tr.trace(root.trace_id))
    assert wall.bloom == pytest.approx(0.01)
    assert wall.redis == pytest.approx(0.25)  # component_s override
    assert wall.p_decode == pytest.approx(0.50)
    assert wall.r_decode == pytest.approx(0.40)


# ---------------------------------------------------------------------------
# Prometheus exposition + fleet merge
# ---------------------------------------------------------------------------

def test_prometheus_text_format():
    reg = MetricsRegistry()
    c = reg.counter("ops_total", "ops served", ("op",))
    c.labels(op="get").inc()
    c.labels(op="get").inc()
    c.labels(op='we"ird\n').inc()            # label escaping
    g = reg.gauge("queue_depth", "jobs waiting")
    g.set(3)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(99.0)
    text = reg.render()
    assert "# HELP ops_total ops served\n# TYPE ops_total counter" in text
    assert 'ops_total{op="get"} 2' in text
    assert 'ops_total{op="we\\"ird\\n"} 1' in text
    assert "# TYPE queue_depth gauge" in text and "queue_depth 3" in text
    # cumulative buckets + +Inf + sum/count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    # linear interpolation within the winning bucket: rank 1.5 of
    # cum counts (1, 2) -> halfway through (0.1, 1.0]
    assert h.quantile(0.5) == pytest.approx(0.55)
    # idempotent re-registration returns the same family
    assert reg.counter("ops_total") is c
    with pytest.raises(ValueError):
        reg.gauge("ops_total")
    with pytest.raises(ValueError):
        c.labels(wrong="x")


def test_merge_snapshots_relabels_per_peer():
    a = MetricsRegistry()
    a.counter("peer_ops_total", "", ("op",)).labels(op="get").inc(3)
    a.histogram("op_seconds").observe(0.2)
    b = MetricsRegistry()
    b.counter("peer_ops_total", "", ("op",)).labels(op="put").inc(1)
    merged = merge_snapshots({"p0": a.snapshot(), "p1": b.snapshot()})
    assert merged["peer_ops_total"]['{peer="p0",op="get"}'] == 3
    assert merged["peer_ops_total"]['{peer="p1",op="put"}'] == 1
    assert merged["op_seconds"]['{peer="p0"}']["count"] == 1


def test_histogram_quantile_interpolates_within_bucket():
    """Pinned p50 regression: the quantile walks cumulative bucket
    counts and interpolates linearly inside the winning bucket — not
    the old snap-to-upper-edge behavior."""
    reg = MetricsRegistry()
    h = reg.histogram("q_seconds", "", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 2.5, 3.5, 6.0):
        h.observe(v)
    # rank 2.5 of cumulative counts (1, 2, 4, 5): quarter-way into
    # the (2, 4] bucket
    assert h.quantile(0.5) == pytest.approx(2.5)
    assert h.quantile(0.2) == pytest.approx(1.0)   # exactly bucket 1
    assert h.quantile(1.0) == pytest.approx(8.0)   # top bucket's edge
    h.observe(100.0)                     # beyond the top edge
    assert h.quantile(1.0) == 8.0        # clamped to the last bucket
    # registration-time bucket config: custom edges drive exposition
    assert 'q_seconds_bucket{le="4"} 4' in reg.render()
    empty = reg.histogram("empty_seconds", "")
    assert empty.quantile(0.5) == 0.0


def test_merge_snapshots_peer_label_collision():
    """Two daemons re-exporting the *same* inner labelset must stay
    distinct series (deterministic relabel, never a silent sum): the
    inner ``peer=`` is renamed ``src_peer=`` and the exporting
    daemon's id takes ``peer=``."""
    a = MetricsRegistry()
    a.counter("repro_catalog_fp_total", "", ("peer",)) \
        .labels(peer="p1").inc(2)
    b = MetricsRegistry()
    b.counter("repro_catalog_fp_total", "", ("peer",)) \
        .labels(peer="p0").inc(5)
    merged = merge_snapshots({"p0": a.snapshot(), "p1": b.snapshot()})
    fam = merged["repro_catalog_fp_total"]
    assert fam['{peer="p0",src_peer="p1"}'] == 2
    assert fam['{peer="p1",src_peer="p0"}'] == 5
    assert len(fam) == 2                 # nothing merged away
    # identical unlabeled families also stay per-peer
    c, d = MetricsRegistry(), MetricsRegistry()
    c.gauge("depth", "").set(1)
    d.gauge("depth", "").set(2)
    m2 = merge_snapshots({"x": c.snapshot(), "y": d.snapshot()})
    assert m2["depth"] == {'{peer="x"}': 1, '{peer="y"}': 2}


def test_mock_clock_swaps_time_sources():
    mc = oclock.MockClock(10.0)
    with oclock.mocked(mc):
        t0 = oclock.monotonic()
        mc.advance(2.5)
        assert oclock.monotonic() - t0 == pytest.approx(2.5)
    assert oclock.monotonic() != 12.5        # real source restored


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------

def test_perfetto_schema(tmp_path):
    tr = Tracer(proc="client")
    with tr.start("infer") as root:
        tr.add("redis.attempt", 0.1, component="redis", peer="p0")
    tr.fold_remote(root, [{"name": "peer.get", "rel_s": 0.0,
                           "dur_s": 0.05, "attrs": {}}], proc="peer:p0")
    doc = perfetto_trace(tr.trace(root.trace_id))
    procs = [e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M"]
    assert set(procs) == {"client", "peer:p0"}     # one track per proc
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 3
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0      # microseconds
        assert e["args"]["trace_id"] == root.trace_id
    att = next(e for e in xs if e["name"] == "redis.attempt")
    assert att["cat"] == "redis"
    assert att["args"]["parent_span"] == root.span_id
    path = write_perfetto(str(tmp_path / "trace.json"),
                          tr.trace(root.trace_id))
    loaded = json.load(open(path))
    assert loaded["displayTimeUnit"] == "ms"
    assert len(loaded["traceEvents"]) == len(doc["traceEvents"])


# ---------------------------------------------------------------------------
# wire interop: the _trace envelope is version negotiation
# ---------------------------------------------------------------------------

def test_extract_trace_is_tolerant():
    assert extract_trace({}) is None
    assert extract_trace({TRACE_KEY: "garbled"}) is None
    assert extract_trace({TRACE_KEY: [1, 2]}) is None
    ctx = extract_trace({TRACE_KEY: ["t", "s"], "key": b"k"})
    assert ctx == SpanContext("t", "s")
    p = inject_trace({"key": b"k"}, NULL_SPAN)
    assert TRACE_KEY not in p                # null span: no envelope


def test_server_interop_with_and_without_trace_ctx():
    """A payload without ``_trace`` is served exactly as before (no
    ``_spans`` in the response — the v2 client path); with the
    envelope, the same op returns server span descriptors."""
    tr_net = InProcTransport(CacheServer(CacheConfig()), SimNetwork(),
                             SimClock())
    blob = b"x" * 64
    resp, _, _ = tr_net.request("put", {"key": b"k" * 32, "blob": blob})
    assert resp["ok"] and SPANS_KEY not in resp      # old-style client
    resp, _, _ = tr_net.request("get", {"key": b"k" * 32})
    assert resp["ok"] and SPANS_KEY not in resp

    tr = Tracer(proc="client")
    with tr.start("infer") as root:
        payload = inject_trace({"key": b"k" * 32}, root)
        resp, _, _ = tr_net.request("get", payload)
    assert resp["ok"] and resp["blob"] == blob       # op unaffected
    descs = resp[SPANS_KEY]
    assert descs and descs[0]["name"] == "peer.get"
    assert descs[0]["dur_s"] >= 0
    n = tr.fold_remote(root, descs, proc="peer:sim")
    assert n == len(descs)
    procs = {d["proc"] for d in tr.trace(root.trace_id)}
    assert {"client", "peer:sim"} <= procs           # one stitched tree


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(capacity=4, max_dumps=2)
    for i in range(10):
        fr.record("fetch.attempt", peer=f"p{i}")
    dump = fr.trigger("plan_exhausted", client="c0", err=ValueError("x"))
    assert dump["reason"] == "plan_exhausted"
    assert dump["context"]["client"] == "c0"
    assert dump["context"]["err"] == repr(ValueError("x"))
    assert len(dump["events"]) == 4                  # ring-bounded
    assert dump["events"][-1]["peer"] == "p9"
    for _ in range(5):
        fr.trigger("shed")
    assert len(fr.dumps()) == 2                      # dumps bounded too
    path = str(tmp_path / "flight.jsonl")
    assert fr.dump_jsonl(path) == 2
    assert len(open(path).readlines()) == 2
    snap = fr.snapshot()
    assert snap["events"] == 4 and snap["dumps"] == 2


def test_flight_dump_jsonl_size_cap(tmp_path):
    """The JSONL spill appends, but stays bounded: past ``max_bytes``
    it rewrites the file with only the retained dumps instead of
    growing the disk forever."""
    fr = FlightRecorder(capacity=4, max_dumps=8)
    for i in range(3):
        fr.trigger("shed", i=i)
    path = str(tmp_path / "flight.jsonl")
    assert fr.dump_jsonl(path) == 3          # append mode by default
    assert fr.dump_jsonl(path) == 3
    assert len(open(path).readlines()) == 6
    size = os.path.getsize(path)
    # file already at/over the cap -> rewritten, not appended
    assert fr.dump_jsonl(path, max_bytes=size) == 3
    assert len(open(path).readlines()) == 3
    # max_bytes=0 disables the cap entirely
    assert fr.dump_jsonl(path, max_bytes=0) == 3
    assert len(open(path).readlines()) == 6


def test_flight_dump_on_injected_chunk_error(tiny_setup):
    """A corrupted chunk stream (injected mid-container) fails the
    streamed fetch with a bounded ChunkError — and freezes a
    ``chunk_error`` flight dump whose ring shows the attempts that led
    up to it."""
    cfg, model, params = tiny_setup
    engine = InferenceEngine(model, params, max_len=512)
    gen = MMLUGenerator(WordHashTokenizer(cfg.vocab), n_shot=2)
    server = CacheServer(CacheConfig())
    clock, net = SimClock(), SimNetwork()

    def client(name, overlap=False):
        return EdgeClient(name, engine,
                          InProcTransport(server, net, clock),
                          CacheConfig(), overlap=overlap)

    client("seed").infer(gen.prompt("virology", 0).segments,
                         max_new_tokens=2)
    for key, blob in list(server.store.items()):
        chunks = state_io.split_container(blob)
        bad = bytearray(chunks[-1])
        bad[len(bad) // 2] ^= 0xFF
        chunks[-1] = bytes(bad)
        server.store[key] = state_io.pack_container(chunks)
    FLIGHT.clear()
    c = client("stream", overlap=True)
    c.sync_catalog()
    res = c.infer(gen.prompt("virology", 1).segments, max_new_tokens=2,
                  upload_on_miss=False)
    assert res.matched_tokens == 0                   # degraded, not hung
    # one dump per corrupt attempt, then plan exhaustion caps the run
    chunk_dumps = [d for d in FLIGHT.dumps()
                   if d["reason"] == CHUNK_ERROR]
    assert chunk_dumps
    dump = chunk_dumps[0]
    assert dump["context"]["client"] == "stream"
    assert "error" in dump["context"]
    # later dumps carry the preceding attempts in their ring (the
    # trigger fires before its own attempt is recorded)
    if len(chunk_dumps) > 1:
        assert any(e["ev"] == "fetch.attempt"
                   for e in chunk_dumps[-1]["events"])
    assert FLIGHT.dumps()[-1]["reason"] == "plan_exhausted"
    FLIGHT.clear()


# ---------------------------------------------------------------------------
# import graph: obs + daemon stay JAX-free
# ---------------------------------------------------------------------------

def test_import_graph_is_jax_free_static():
    """R1 of the project checker: the full static import closure of the
    peer daemon (which includes repro.obs) is JAX/numpy-free. This
    replaces the old per-module subprocess probes — the static walk
    covers every module the interpreter would execute at daemon import
    time, not just the two roots the old test happened to spawn."""
    from repro.analysis import run_rules
    from repro.analysis.core import load_tree
    findings = run_rules(load_tree(SRC), rules=("R1",))
    assert not findings, "\n".join(f.render() for f in findings)


def test_annotation_hook_is_installed_from_the_serving_side():
    """``repro.obs`` imports no profiler and installs no annotation;
    importing the serving engine installs JAX's."""
    code = ("import sys; from repro.obs import trace;"
            "assert trace._annotate is None;"
            "assert 'jax' not in sys.modules, 'jax imported by repro.obs';"
            "import repro.serving.engine as e;"
            "assert trace._annotate is e._annotation")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr + proc.stdout


def test_import_graph_is_jax_free_runtime_smoke():
    """Thin runtime twin of the static R1 check: actually spawn the
    daemon import once and confirm no jax/numpy module materializes
    (guards dynamic imports the AST walk cannot see)."""
    code = ("import importlib, sys;"
            "importlib.import_module('repro.core.net.daemon');"
            "bad = sorted(m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'numpy'));"
            "sys.exit(f'ML runtime leaked: {bad}' if bad else 0)")
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr + proc.stdout


def test_infer_result_carries_trace_id(tiny_setup):
    """EdgeClient.infer returns the trace id; the client tracer
    resolves it to the span tree whose projection is the wall
    breakdown."""
    cfg, model, params = tiny_setup
    engine = InferenceEngine(model, params, max_len=512)
    gen = MMLUGenerator(WordHashTokenizer(cfg.vocab), n_shot=2)
    c = EdgeClient("t", engine,
                   InProcTransport(CacheServer(CacheConfig()),
                                   SimNetwork(), SimClock()),
                   CacheConfig())
    res = c.infer(gen.prompt("virology", 0).segments, max_new_tokens=2)
    assert res.trace_id
    spans = c.tracer.trace(res.trace_id)
    names = {d["name"] for d in spans}
    assert "infer" in names and "bloom" in names
    assert Breakdown.from_spans(spans).p_decode == \
        pytest.approx(res.wall.p_decode)
