"""The HTTP front door: OpenAI wire compat, SSE framing, quotas, load
shedding, token identity with the in-process scheduler, and the Fabric
facade (equivalence across backends + deprecation shims)."""
import http.client
import json
import time
import warnings

import jax
import numpy as np
import pytest

from repro.config import CacheConfig
from repro.core import (CacheServer, EdgeClient, Fabric, FetchPolicy,
                        SessionPool, SimClock, SimNetwork)
from repro.core.metrics import RequestStats, ServingReport
from repro.core.transport import InProcTransport
from repro.data import MMLUGenerator, WordHashTokenizer
from repro.gateway import Gateway, GatewayEngine, TenantQuota
from repro.gateway import protocol
from repro.gateway.admission import AdmissionController, ShedError
from repro.serving.engine import BatchedEngine, InferenceEngine
from repro.serving.scheduler import Request, Scheduler

MAX_LEN = 128


# ---------------------------------------------------------------------------
# HTTP helpers (stdlib only — the gateway has no client SDK on purpose)
# ---------------------------------------------------------------------------

def _conn(gw):
    return http.client.HTTPConnection("127.0.0.1", gw.port, timeout=60)


def _post(gw, path, body, headers=None):
    c = _conn(gw)
    raw = json.dumps(body) if isinstance(body, dict) else body
    c.request("POST", path, raw,
              {"Content-Type": "application/json", **(headers or {})})
    r = c.getresponse()
    data = r.read()
    c.close()
    return r, data


def _get(gw, path):
    c = _conn(gw)
    c.request("GET", path)
    r = c.getresponse()
    data = r.read()
    c.close()
    return r, data


class _StreamReq:
    """A streaming request held open: admitted once the first SSE token
    arrives, released when drained/closed."""

    def __init__(self, gw, body, path="/v1/completions"):
        self.conn = _conn(gw)
        self.conn.request("POST", path, json.dumps(body),
                          {"Content-Type": "application/json"})
        self.resp = self.conn.getresponse()

    def wait_first_token(self, timeout_s=30.0):
        assert self.resp.status == 200
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            line = self.resp.readline()
            if line.startswith(b"data: ") and b"token_id" in line:
                return
        raise AssertionError("no SSE token before timeout")

    def drain(self):
        self.resp.read()
        self.conn.close()


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gw(tiny_setup):
    cfg, model, params = tiny_setup
    quotas = {
        "limited": TenantQuota(max_concurrent=8, rate_per_s=0.001,
                               burst=1),
        "narrow": TenantQuota(max_concurrent=1),
    }
    g = Gateway(model, params, fabric=Fabric.local(), batch_size=2,
                max_len=MAX_LEN, quotas=quotas,
                model_name="test-model").start()
    yield g
    g.stop()


@pytest.fixture(scope="module")
def tok(tiny_setup):
    return WordHashTokenizer(tiny_setup[0].vocab)


def _direct_tokens(model, params, tok, prompt_or_messages, max_new):
    """Reference run: same tokenization, fresh scheduler, no cache."""
    if isinstance(prompt_or_messages, str):
        segs = protocol.tokenize_prompt(tok, prompt_or_messages)
    else:
        segs = protocol.tokenize_messages(tok, prompt_or_messages)
    eng = BatchedEngine(model, params, max_len=MAX_LEN, batch_size=1)
    sched = Scheduler(eng)
    req = Request(tokens=np.asarray(segs.token_ids, np.int32),
                  max_new_tokens=max_new)
    sched.run([req])
    return req.stats.output_tokens


# ---------------------------------------------------------------------------
# OpenAI wire behaviour + token identity
# ---------------------------------------------------------------------------

def test_completion_token_identity(gw, tiny_setup, tok):
    cfg, model, params = tiny_setup
    prompt = "compare the two routing strategies in detail"
    r, data = _post(gw, "/v1/completions",
                    {"prompt": prompt, "max_tokens": 6, "model": "m"})
    assert r.status == 200
    body = json.loads(data)
    assert body["object"] == "text_completion"
    assert body["usage"]["completion_tokens"] == 6
    assert body["choices"][0]["finish_reason"] == "length"
    expect = _direct_tokens(model, params, tok, prompt, 6)
    assert body["choices"][0]["token_ids"] == list(expect)


def test_chat_token_identity_and_cache_hit(gw, tiny_setup, tok):
    cfg, model, params = tiny_setup
    msgs = [{"role": "system", "content": "terse assistant"},
            {"role": "user", "content": "name a planet"}]
    body = {"messages": msgs, "max_tokens": 5}
    r1, d1 = _post(gw, "/v1/chat/completions", body)
    assert r1.status == 200
    first = json.loads(d1)
    assert first["object"] == "chat.completion"
    assert first["cache"]["matched_tokens"] == 0
    gw.engine.fetcher.flush_uploads()
    r2, d2 = _post(gw, "/v1/chat/completions", body)
    second = json.loads(d2)
    # second run resumes from the uploaded prefix, tokens identical
    assert second["cache"]["matched_tokens"] > 0
    assert second["choices"][0]["token_ids"] == \
        first["choices"][0]["token_ids"]
    expect = _direct_tokens(model, params, tok,
                            [(m["role"], m["content"]) for m in msgs], 5)
    assert first["choices"][0]["token_ids"] == list(expect)


def test_sse_chunk_framing(gw, tiny_setup, tok):
    cfg, model, params = tiny_setup
    body = {"messages": [{"role": "user", "content": "stream me a song"}],
            "max_tokens": 4, "stream": True}
    r, data = _post(gw, "/v1/chat/completions", body)
    assert r.status == 200
    assert r.getheader("Content-Type") == "text/event-stream"
    events = [e for e in data.split(b"\n\n") if e]
    assert all(e.startswith(b"data: ") for e in events)
    assert events[-1] == b"data: [DONE]"
    chunks = [json.loads(e[6:]) for e in events[:-1]]
    assert all(c["object"] == "chat.completion.chunk" for c in chunks)
    toks = [c["choices"][0]["token_id"] for c in chunks
            if "token_id" in c["choices"][0]]
    finishes = [c["choices"][0]["finish_reason"] for c in chunks
                if c["choices"][0]["finish_reason"]]
    assert finishes == ["length"]          # exactly one terminal chunk
    assert chunks[-1]["choices"][0]["delta"] == {}
    expect = _direct_tokens(model, params, tok,
                            [("user", "stream me a song")], 4)
    assert toks == list(expect)


def test_malformed_requests_get_400(gw):
    cases = [
        b"{not json",
        {"max_tokens": 4},                              # no prompt
        {"prompt": ""},                                 # empty prompt
        {"prompt": 42},                                 # wrong type
        {"prompt": "x", "max_tokens": 0},
        {"prompt": "x", "max_tokens": True},
        {"prompt": "x", "max_tokens": 10_000},          # over cap
        {"prompt": "x", "temperature": 0.7},            # not greedy
        {"prompt": "x", "stream": "yes"},
        {"prompt": "x", "user": 3},
        {"prompt": "word " * 500},                      # over max_len
    ]
    for body in cases:
        r, data = _post(gw, "/v1/completions", body)
        assert r.status == 400, body
        assert "message" in json.loads(data)["error"]
    chat_cases = [
        {"messages": []},
        {"messages": "hi"},
        {"messages": [{"role": "robot", "content": "x"}]},
        {"messages": [{"role": "user", "content": ""}]},
        {"messages": [{"role": "user"}]},
    ]
    for body in chat_cases:
        r, _ = _post(gw, "/v1/chat/completions", body)
        assert r.status == 400, body


def test_routing_and_introspection(gw):
    r, _ = _get(gw, "/no/such/route")
    assert r.status == 404
    r, _ = _get(gw, "/v1/completions")                  # wrong method
    assert r.status == 405 and r.getheader("Allow") == "POST"
    r, data = _get(gw, "/healthz")
    health = json.loads(data)
    assert r.status == 200 and health["ok"] and health["slots"] == 2
    r, data = _get(gw, "/v1/models")
    assert json.loads(data)["data"][0]["id"] == "test-model"
    r, data = _get(gw, "/metrics.json")
    metrics = json.loads(data)
    assert "report" in metrics and "admission" in metrics
    assert metrics["admission"]["max_inflight"] == 2
    r, data = _get(gw, "/metrics")
    assert r.status == 200
    assert r.getheader("Content-Type").startswith("text/plain")
    text = data.decode()
    assert "# TYPE gateway_http_requests_total counter" in text
    assert "gateway_ttft_seconds_bucket" in text


def test_miss_trace_holds_inbox_wait_and_upload_under_one_root(gw):
    """A miss's tree: the inbox wait and the upload (slot copy, pull to
    the host, encoding) live under its ``gw.request`` root; the engine
    ring serves the iterations, and the inbox wait is a histogram."""
    body = {"prompt": "a prompt nobody sent before, traced", "max_tokens": 3}
    r, data = _post(gw, "/v1/completions", body)
    assert r.status == 200
    first = json.loads(data)
    assert first["cache"]["matched_tokens"] == 0
    r, data = _get(gw, f"/v1/traces/{first['id']}")
    assert r.status == 200
    spans = json.loads(data)["spans"]
    by_name = {d["name"]: d for d in spans}
    assert {"gw.inbox_wait", "gw.resolve", "catalog.sync", "gw.upload",
            "upload.slice", "state.serialize", "upload.to_host",
            "upload.encode"} <= set(by_name)
    roots = [d for d in spans if not d["parent"]]
    assert len(roots) == 1 and roots[0]["name"] == "gw.request"
    root = roots[0]["span"]
    assert by_name["gw.inbox_wait"]["parent"] == root
    assert by_name["gw.upload"]["parent"] == root
    up = by_name["gw.upload"]["span"]
    assert by_name["upload.slice"]["parent"] == up
    assert by_name["state.serialize"]["parent"] == up
    ser = by_name["state.serialize"]["span"]
    assert by_name["upload.to_host"]["parent"] == ser
    # the slot crosses to the host only as far as the prompt's bucket:
    # 64 of its 128 positions
    assert by_name["gw.upload"]["attrs"]["pulled_positions"] == 64
    leaves = jax.tree.leaves(gw.engine.engine.cache)
    assert all(a.shape[2] == MAX_LEN for a in leaves)
    assert by_name["upload.to_host"]["attrs"]["nbytes"] == sum(
        a.nbytes // a.shape[1] // MAX_LEN * 64 for a in leaves)
    assert by_name["gw.upload"]["attrs"]["packed_bytes"] > 0
    assert by_name["catalog.sync"]["parent"] == \
        by_name["gw.resolve"]["span"]
    r, data = _get(gw, "/v1/traces/engine")
    assert r.status == 200
    ring = json.loads(data)
    assert ring["trace_id"] == "engine"
    names = {d["name"] for d in ring["spans"]}
    assert {"eng.step", "eng.wait", "eng.prefill", "prefill.wait",
            "eng.decode", "decode.wait", "eng.sample"} <= names
    text = _get(gw, "/metrics")[1].decode()
    assert "gateway_inbox_wait_seconds_bucket" in text
    assert "gateway_requests_finished_total" not in text


def test_untraced_gateway_serves_uploads_and_hits(tiny_setup, tok):
    """With the tracer off the engine thread times nothing, yet a miss
    still answers the reference's tokens and uploads its prefix, which
    the next identical prompt then hits."""
    from repro.obs.trace import NULL_TRACER
    cfg, model, params = tiny_setup
    g = Gateway(model, params, fabric=Fabric.local(), batch_size=2,
                max_len=MAX_LEN, tracer=NULL_TRACER).start()
    try:
        prompt = "an untraced prompt that is uploaded on its miss"
        body = {"prompt": prompt, "max_tokens": 4}
        r, data = _post(g, "/v1/completions", body)
        assert r.status == 200
        first = json.loads(data)
        assert first["cache"]["matched_tokens"] == 0
        assert first["choices"][0]["token_ids"] == list(
            _direct_tokens(model, params, tok, prompt, 4))
        assert g.engine.fetcher.flush_uploads()
        assert g.engine.fetcher.stats["uploads"] > 0
        assert g.engine.fetcher.stats["upload_errors"] == 0
        r, data = _post(g, "/v1/completions", body)
        hit = json.loads(data)
        assert hit["cache"]["matched_tokens"] > 0
        assert hit["choices"][0]["token_ids"] == \
            first["choices"][0]["token_ids"]
        assert g.tracer.spans() == []
    finally:
        g.stop()


def test_keepalive_pipelines_sequential_requests(gw):
    """Two unary requests down ONE socket: the server must answer both
    (Connection: keep-alive), count the reuse, and link the second
    request's root span to the first via the ``follows`` attr."""
    before = gw.server.stats["keepalive_reuses"]
    c = _conn(gw)
    try:
        tids = []
        for i in range(2):
            c.request("POST", "/v1/completions",
                      json.dumps({"prompt": f"keepalive req {i}",
                                  "max_tokens": 2}),
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            assert r.status == 200
            assert r.getheader("Connection") == "keep-alive"
            tids.append(json.loads(r.read())["cache"]["trace_id"])
    finally:
        c.close()
    assert gw.server.stats["keepalive_reuses"] >= before + 1

    def _root(tid):
        # the root span ends just after the response bytes flush —
        # give the server's event loop a beat to record it
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            for s in (gw.tracer.trace(tid) or []):
                if s["name"] == "gw.request":
                    return s
            time.sleep(0.01)
        raise AssertionError(f"gw.request root never recorded for {tid}")

    roots = [_root(t) for t in tids]
    assert roots[0]["attrs"]["conn"] == roots[1]["attrs"]["conn"]
    assert roots[0]["attrs"]["seq"] == 0 and roots[1]["attrs"]["seq"] == 1
    assert roots[1]["attrs"]["follows"] == roots[0]["span"]
    assert "follows" not in roots[0]["attrs"]


def test_connection_close_honoured(gw):
    c = _conn(gw)
    try:
        c.request("GET", "/healthz", headers={"Connection": "close"})
        r = c.getresponse()
        assert r.status == 200
        assert r.getheader("Connection") == "close"
        r.read()
    finally:
        c.close()


# ---------------------------------------------------------------------------
# quotas + load shedding
# ---------------------------------------------------------------------------

def test_rate_quota_sheds_429(gw):
    body = {"prompt": "rate limited tenant", "max_tokens": 2,
            "user": "limited"}                # burst=1, ~no refill
    r1, _ = _post(gw, "/v1/completions", body)
    assert r1.status == 200
    r2, data = _post(gw, "/v1/completions", body)
    assert r2.status == 429
    assert int(r2.getheader("Retry-After")) >= 1
    assert json.loads(data)["error"]["type"] == "rate_limit_exceeded"


def test_tenant_concurrency_sheds_429(gw):
    hold = _StreamReq(gw, {"prompt": "hold this slot open for a while",
                           "max_tokens": 100, "stream": True,
                           "user": "narrow"})
    try:
        hold.wait_first_token()
        r, _ = _post(gw, "/v1/completions",
                     {"prompt": "second concurrent", "max_tokens": 2,
                      "user": "narrow"})
        assert r.status == 429
        assert r.getheader("Retry-After") is not None
    finally:
        hold.drain()


def test_capacity_sheds_503_under_slot_exhaustion(tiny_setup):
    """One slot, zero queue: a held stream exhausts the gateway and the
    next request is refused with 503 + Retry-After, not queued."""
    cfg, model, params = tiny_setup
    g = Gateway(model, params, fabric=None, batch_size=1,
                max_len=MAX_LEN, max_inflight=1, queue_depth=0).start()
    try:
        hold = _StreamReq(g, {"prompt": "exhaust the only slot",
                              "max_tokens": 100, "stream": True})
        hold.wait_first_token()
        r, data = _post(g, "/v1/completions",
                        {"prompt": "overflow", "max_tokens": 2})
        assert r.status == 503
        assert r.getheader("Retry-After") is not None
        assert json.loads(data)["error"]["type"] == "overloaded"
        hold.drain()
        # capacity freed: the same request is admitted now
        r, _ = _post(g, "/v1/completions",
                     {"prompt": "overflow", "max_tokens": 2})
        assert r.status == 200
    finally:
        g.stop()


def test_x_tenant_header_overrides_body_user(gw):
    r, _ = _post(gw, "/v1/completions",
                 {"prompt": "who am i", "max_tokens": 2, "user": "body"},
                 headers={"X-Tenant": "header"})
    assert r.status == 200
    snap = gw.admission.snapshot()
    assert "header" in snap["tenants"]


def test_admission_controller_units():
    adm = AdmissionController(max_inflight=2, queue_depth=0,
                              default_quota=TenantQuota(
                                  max_concurrent=1, rate_per_s=1.0,
                                  burst=2))
    adm.admit("a")
    with pytest.raises(ShedError) as ei:
        adm.admit("a")                       # concurrency before rate
    assert ei.value.status == 429
    adm.admit("b")
    with pytest.raises(ShedError) as ei:
        adm.admit("c")                       # global capacity
    assert ei.value.status == 503
    adm.release("a", latency_s=0.2)
    adm.admit("c")
    assert adm.shed_counts() == {"a": 1, "c": 1}
    with pytest.raises(ValueError):
        TenantQuota(max_concurrent=0)


# ---------------------------------------------------------------------------
# FetchPolicy (satellite: contradictory combos rejected at construction)
# ---------------------------------------------------------------------------

def test_fetch_policy_validation():
    with pytest.raises(ValueError):
        FetchPolicy(transfer="warp")
    with pytest.raises(ValueError):
        FetchPolicy(transfer="blocking", overlap=True)
    with pytest.raises(ValueError):
        FetchPolicy(transfer="streamed", overlap=False)
    with pytest.raises(ValueError):
        FetchPolicy(min_match_tokens=-1)
    p = FetchPolicy()                        # defaults are coherent
    assert p.transfer == "auto" and p.use_catalog


def test_edge_client_rejects_policy_plus_legacy_flags(tiny_setup):
    cfg, model, params = tiny_setup
    engine = InferenceEngine(model, params, max_len=MAX_LEN)
    tr = InProcTransport(CacheServer(CacheConfig()), SimNetwork(),
                         SimClock())
    with pytest.raises(ValueError, match="not both"):
        EdgeClient("dup", engine, tr, CacheConfig(),
                   policy=FetchPolicy(), overlap=True)


def test_gateway_engine_rejects_streamed_policy(tiny_setup):
    cfg, model, params = tiny_setup
    with pytest.raises(ValueError, match="blocking"):
        GatewayEngine(model, params,
                      policy=FetchPolicy(transfer="streamed",
                                         overlap=True))


# ---------------------------------------------------------------------------
# Fabric facade: backend equivalence + deprecation shims
# ---------------------------------------------------------------------------

def _pool_tokens(fabric, engine, gen, n=3):
    pool = SessionPool(engine=engine, fabric=fabric, n_sessions=2,
                       cache_cfg=CacheConfig())
    jobs = [gen.prompt("astronomy", q).segments for q in range(n)]
    return [r.output_tokens for r in pool.run(jobs, max_new_tokens=4)]


def test_fabric_equivalence_sim_vs_local(tiny_setup):
    cfg, model, params = tiny_setup
    engine = InferenceEngine(model, params, max_len=512)
    gen = MMLUGenerator(WordHashTokenizer(cfg.vocab), n_shot=2)
    toks_local = _pool_tokens(Fabric.local(), engine, gen)
    toks_sim = _pool_tokens(Fabric.sim(n_peers=2), engine, gen)
    assert toks_local == toks_sim


@pytest.mark.slow
def test_gateway_trace_spans_client_and_remote_daemon(tiny_setup):
    """Acceptance: a gateway request id resolves via GET
    /v1/traces/<id> to ONE span tree that crosses process boundaries —
    gateway-side request/resolve/slot spans plus folded remote spans
    minted by a peer daemon (its pid rides along as proof)."""
    cfg, model, params = tiny_setup
    with Fabric.tcp(n_peers=2) as fabric:
        g = Gateway(model, params, fabric=fabric, batch_size=2,
                    max_len=MAX_LEN).start()
        try:
            body = {"prompt": "trace me across the fleet",
                    "max_tokens": 3}
            r1, d1 = _post(g, "/v1/completions", body)
            assert r1.status == 200
            g.engine.fetcher.flush_uploads()
            # the miss: inbox wait and upload under its one root
            miss = json.loads(_get(g, "/v1/traces/"
                                   f"{json.loads(d1)['id']}")[1])["spans"]
            assert {"gw.inbox_wait", "gw.upload", "upload.slice",
                    "upload.to_host", "upload.encode"} <= \
                {d["name"] for d in miss}
            assert [d["name"] for d in miss if not d["parent"]] == \
                ["gw.request"]
            # retry until the uploaded prefix is visible through the
            # gossiped catalog and a daemon actually serves the hit
            deadline = time.monotonic() + 60
            second = None
            while time.monotonic() < deadline:
                _, d2 = _post(g, "/v1/completions", body)
                second = json.loads(d2)
                if second["cache"]["matched_tokens"] > 0:
                    break
                time.sleep(0.3)
            assert second["cache"]["matched_tokens"] > 0
            rid = second["id"]
            assert second["cache"]["trace_id"]
            r, data = _get(g, f"/v1/traces/{rid}")   # alias lookup
            assert r.status == 200
            doc = json.loads(data)
            assert doc["trace_id"] == second["cache"]["trace_id"]
            spans = doc["spans"]
            names = {d["name"] for d in spans}
            assert "gw.request" in names and "gw.resolve" in names
            assert {"gw.inbox_wait", "catalog.sync", "slot.queue_wait",
                    "slot.prefill", "slot.decode"} <= names
            assert "gw.upload" not in names          # a hit uploads nothing
            # cross-process: folded spans minted by the daemon process
            remote = [d for d in spans
                      if str(d["proc"]).startswith("peer:")]
            assert remote
            assert any(d["attrs"].get("pid") for d in remote)
            assert all(d["attrs"].get("remote") for d in remote)
            # one connected tree, rooted at the HTTP front door
            roots = [d for d in spans if not d["parent"]]
            assert len(roots) == 1 and roots[0]["name"] == "gw.request"
            assert doc["tree"]["name"] == "gw.request"
            # unknown ids 404
            r, _ = _get(g, "/v1/traces/nope")
            assert r.status == 404
            # flight endpoint serves the ring snapshot
            r, data = _get(g, "/v1/flight")
            assert r.status == 200 and "snapshot" in json.loads(data)
        finally:
            g.stop()


@pytest.mark.slow
def test_fabric_equivalence_tcp(tiny_setup):
    cfg, model, params = tiny_setup
    engine = InferenceEngine(model, params, max_len=512)
    gen = MMLUGenerator(WordHashTokenizer(cfg.vocab), n_shot=2)
    toks_local = _pool_tokens(Fabric.local(), engine, gen)
    with Fabric.tcp(n_peers=2) as fabric:
        toks_tcp = _pool_tokens(fabric, engine, gen)
    assert toks_tcp == toks_local


def test_deprecated_constructors_still_work_and_warn(tiny_setup):
    cfg, model, params = tiny_setup
    engine = InferenceEngine(model, params, max_len=512)
    gen = MMLUGenerator(WordHashTokenizer(cfg.vocab), n_shot=2)
    server = CacheServer(CacheConfig())
    with pytest.warns(DeprecationWarning, match="Fabric"):
        pool = SessionPool(server, engine, n_sessions=1)
    res = pool.run([gen.prompt("virology", 0).segments],
                   max_new_tokens=2)
    assert len(res[0].output_tokens) == 2
    # the new spelling is warning-free
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        SessionPool(engine=engine, fabric=Fabric.local(), n_sessions=1)


# ---------------------------------------------------------------------------
# ServingReport: per-tenant slices + shed counts (satellite)
# ---------------------------------------------------------------------------

def _stats(rid, tenant, ttft=0.1, lat=0.5, n_out=4):
    return RequestStats(req_id=rid, prompt_tokens=8,
                        output_tokens=list(range(n_out)), submit_t=1.0,
                        admit_t=1.0, first_token_t=1.0 + ttft,
                        finish_t=1.0 + lat, finish_reason="length",
                        tenant=tenant)


def test_serving_report_per_tenant_and_shed():
    reqs = [_stats(0, "a", ttft=0.1), _stats(1, "a", ttft=0.3),
            _stats(2, "b", ttft=0.2)]
    rep = ServingReport.from_requests(reqs, wall_s=2.0,
                                      shed={"a": 1, "c": 2})
    assert rep.shed_requests == 3
    assert set(rep.per_tenant) == {"a", "b", "c"}
    assert rep.per_tenant["a"].n_requests == 2
    assert rep.per_tenant["a"].shed == 1
    assert rep.per_tenant["c"].n_requests == 0   # shed-only tenant
    d = rep.as_dict()
    assert d["per_tenant"]["b"]["ttft_p50"] == pytest.approx(0.2)


def test_serving_report_untagged_round_trips_unchanged():
    """Old-style runs (no tenants, no shedding) keep the old shape."""
    reqs = [_stats(0, ""), _stats(1, "")]
    rep = ServingReport.from_requests(reqs, wall_s=1.0)
    assert rep.per_tenant == {} and rep.shed_requests == 0
    d = rep.as_dict()
    assert d["n_requests"] == 2 and d["per_tenant"] == {}
