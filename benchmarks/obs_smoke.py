"""Observability smoke: tracing must be near-free and the telemetry real.

Replays a short customer-support mix through the HTTP gateway twice —
once with the default tracer, once with ``NULL_TRACER`` — and asserts:

* **telemetry is real**: after the traced replay, a real HTTP scrape of
  ``GET /metrics`` contains the key Prometheus series
  (``gateway_ttft_seconds_bucket``, ``gateway_http_requests_total``,
  ``sched_requests_total``, ``gateway_request_seconds``) and the last
  request's ``cache.trace_id`` resolves via ``GET /v1/traces/<id>`` to
  a span tree containing ``gw.request`` and the slot lifecycle;
* **tracing is near-free**: client-observed p50 TTFT with tracing on
  regresses < 2% vs tracing off (plus a small absolute epsilon — these
  are millisecond-scale reduced-model requests). Best-of-``ATTEMPTS``
  replays on the same warmed gateways, so one noisy run on a shared CI
  box doesn't fail the job.

Emits ``BENCH_obs_smoke.json`` (p50s, overhead fraction, series seen,
span rollup). Usage::

    PYTHONPATH=src python -m benchmarks.obs_smoke [--quick]
"""
from __future__ import annotations

import http.client
import json
import sys
import time

from benchmarks.common import csv_line, write_bench
from repro.configs import get_config
from repro.gateway import Gateway, TenantQuota
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.obs.trace import NULL_TRACER
from repro.workloads import MIXES

MAX_LEN = 384
KEY_SERIES = (
    "# TYPE gateway_ttft_seconds histogram",
    "gateway_ttft_seconds_bucket",
    "gateway_http_requests_total",
    "gateway_request_seconds_count",
    "sched_requests_total",
    "sched_queue_wait_seconds_bucket",
)
ATTEMPTS = 3          # best-of replays for the overhead comparison
EPS_S = 2e-3          # absolute slack on top of the 2% bound


def _stream_ttft(host: str, port: int, wl) -> float:
    """One SSE request; returns client-observed TTFT seconds."""
    t0 = time.perf_counter()
    conn = http.client.HTTPConnection(host, port, timeout=120)
    try:
        conn.request("POST", "/v1/chat/completions",
                     json.dumps(wl.body(stream=True)),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, resp.status
        ttft = None
        while True:
            line = resp.readline()
            if not line:
                break
            if ttft is None and line.startswith(b"data:") \
                    and b"[DONE]" not in line:
                ttft = time.perf_counter() - t0
        assert ttft is not None, "stream produced no tokens"
        return ttft
    finally:
        conn.close()


def _unary(host: str, port: int, wl) -> dict:
    conn = http.client.HTTPConnection(host, port, timeout=120)
    try:
        conn.request("POST", "/v1/chat/completions",
                     json.dumps(wl.body()),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, resp.status
        return json.loads(resp.read())
    finally:
        conn.close()


def _get(host: str, port: int, path: str):
    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _p50(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def replay_p50(gw: Gateway, reqs) -> float:
    return _p50([_stream_ttft(gw.server.host, gw.port, wl)
                 for wl in reqs])


def check_telemetry(gw: Gateway, wl, out: dict) -> None:
    """Scrape /metrics over real HTTP + resolve one request's trace."""
    status, body = _get(gw.server.host, gw.port, "/metrics")
    assert status == 200, status
    text = body.decode()
    missing = [s for s in KEY_SERIES if s not in text]
    assert not missing, f"missing Prometheus series: {missing}"
    out["metrics_series_ok"] = list(KEY_SERIES)

    resp = _unary(gw.server.host, gw.port, wl)
    tid = resp.get("cache", {}).get("trace_id", "")
    assert tid, f"unary response carried no trace_id: {resp.get('cache')}"
    status, body = _get(gw.server.host, gw.port, f"/v1/traces/{tid}")
    assert status == 200, status
    tree = json.loads(body)
    names = {s["name"] for s in tree["spans"]}
    need = {"gw.request", "gw.parse", "slot.prefill", "slot.decode"}
    assert need <= names, f"trace missing spans: {need - names}"
    out["trace_resolved"] = {"trace_id": tid, "n_spans": tree["n_spans"]}


def main():
    enable_compile_cache()
    quick = "--quick" in sys.argv
    n = 8 if quick else 24
    warm = 2 if quick else 4

    cfg = get_config("gemma3-270m").reduced()
    model = Model(cfg)
    import jax
    params = model.init(jax.random.PRNGKey(0))
    reqs = MIXES["support"](n + warm + 1, seed=0, rate_per_s=0.0,
                            max_new_tokens=4)

    def mk(tracer=None):
        return Gateway(model, params, batch_size=2, max_len=MAX_LEN,
                       max_inflight=8, queue_depth=8,
                       default_quota=TenantQuota(max_concurrent=8),
                       model_name="obs-smoke", tracer=tracer).start()

    lines, out = [], {"n_per_replay": n}
    gw_on, gw_off = mk(), mk(tracer=NULL_TRACER)
    try:
        for gw in (gw_on, gw_off):                       # JIT warmup
            replay_p50(gw, reqs[:warm])
        check_telemetry(gw_on, reqs[warm], out)

        # all ATTEMPTS trials run (no early break): the bound applies
        # to the min, but every trial lands in the BENCH json so a
        # noisy CI box is visible in the artifact, not hidden by the
        # first lucky pair
        trials_on, trials_off = [], []
        for _ in range(ATTEMPTS):
            trials_off.append(replay_p50(gw_off, reqs[warm + 1:]))
            trials_on.append(replay_p50(gw_on, reqs[warm + 1:]))
        best_on, best_off = min(trials_on), min(trials_off)
        overhead = best_on / best_off - 1.0
        out.update(ttft_p50_on_s=best_on, ttft_p50_off_s=best_off,
                   trials_on_s=trials_on, trials_off_s=trials_off,
                   overhead_frac=overhead)
        assert best_on <= best_off * 1.02 + EPS_S, (
            f"tracing overhead {overhead:+.1%} exceeds 2% "
            f"(on={best_on * 1e3:.2f}ms off={best_off * 1e3:.2f}ms)")
        out["overhead_ok"] = True
        lines.append(csv_line(
            "obs_smoke", best_on * 1e6,
            f"overhead={overhead:+.1%};"
            f"series={len(KEY_SERIES)}ok;"
            f"trace_spans={out['trace_resolved']['n_spans']}"))
        spans = gw_on.tracer.rollup()
    finally:
        gw_on.stop()
        gw_off.stop()

    write_bench("BENCH_obs_smoke.json", out, spans=spans)
    return lines


if __name__ == "__main__":
    main()
