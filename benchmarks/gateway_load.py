"""Gateway load replay: three production mixes over a real TCP fabric.

End-to-end through every layer this repo has: seeded workload
generators (``repro.workloads``) -> HTTP/SSE against the OpenAI-style
gateway -> continuous-batching scheduler -> blocking prompt-cache
resolve/upload against a ``Fabric.tcp`` fleet of real
``PeerSupervisor`` daemon processes.

Per mix it reports client-observed TTFT/TTLT p50/p95, shed rate,
cache traffic, and a nominal cost-per-1K-requests (device-hours +
egress). Two acceptance checks run inline:

* **token identity** — every gateway completion must match a direct
  in-process ``Scheduler`` run of the same prompt (greedy, same
  model/params/max_len), cache hits included;
* **bounded shedding** — a burst against a 1-slot gateway must shed
  with 429/503 + ``Retry-After`` instead of queueing unboundedly.

Emits ``BENCH_gateway_load.json``. Usage::

    PYTHONPATH=src python -m benchmarks.gateway_load [--quick] [--mix m]
"""
from __future__ import annotations

import argparse
import http.client
import json
import threading
import time

import numpy as np

from benchmarks.common import csv_line, merge_rollups, write_bench
from repro.config import CacheConfig
from repro.configs import get_config
from repro.core import Fabric
from repro.data import WordHashTokenizer
from repro.gateway import Gateway, TenantQuota, protocol
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serving import BatchedEngine, Request, Scheduler
from repro.workloads import MIXES

MAX_LEN = 384
MAX_NEW = 8
# nominal fleet economics: edge device $/hr per box, LAN egress $/GB
DEVICE_USD_PER_HR = 0.12
EGRESS_USD_PER_GB = 0.02


# ---------------------------------------------------------------------------
# HTTP replay client (stdlib only; SSE readline gives client-side TTFT)
# ---------------------------------------------------------------------------

def _stream_one(host: str, port: int, wl, out: dict) -> None:
    t0 = time.perf_counter()
    try:
        conn = http.client.HTTPConnection(host, port, timeout=120)
        conn.request("POST", "/v1/chat/completions",
                     json.dumps(wl.body(stream=True)),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out["status"] = resp.status
        if resp.status != 200:
            out["retry_after"] = resp.getheader("Retry-After")
            resp.read()
            return
        tokens = []
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            payload = line[6:].strip()
            if payload == b"[DONE]":
                break
            chunk = json.loads(payload)
            out["id"] = chunk.get("id", out.get("id"))
            choice = chunk["choices"][0]
            if "token_id" in choice:
                if not tokens:
                    out["ttft_s"] = time.perf_counter() - t0
                tokens.append(choice["token_id"])
        out["ttlt_s"] = time.perf_counter() - t0
        out["tokens"] = tokens
    except Exception as e:            # noqa: BLE001 — record, don't hang
        out["error"] = repr(e)
    finally:
        try:
            conn.close()
        except Exception:
            pass


def replay(gw, requests, time_scale: float = 1.0):
    """Fire each request at its (scaled) arrival offset, concurrently."""
    results = [dict() for _ in requests]
    t0 = time.perf_counter()

    def worker(i, wl):
        delay = wl.arrival_s * time_scale - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        _stream_one(gw.server.host, gw.port, wl, results[i])

    threads = [threading.Thread(target=worker, args=(i, wl), daemon=True)
               for i, wl in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
    wall = time.perf_counter() - t0
    return results, wall


def _get_json(gw, path: str):
    conn = http.client.HTTPConnection(gw.server.host, gw.port,
                                      timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# acceptance: every served request resolves to a populated decision
# ---------------------------------------------------------------------------

def resolve_decisions(gw, results, name: str) -> dict:
    """``GET /v1/decisions/<completion-id>`` for every 200 response:
    each must resolve to a record with its candidate set and a
    committed outcome (realized timings, regret)."""
    checked, regret, savings = 0, 0.0, 0.0
    by_result: dict = {}
    for r in results:
        if r.get("status") != 200:
            continue
        rid = r.get("id")
        assert rid, f"{name}: streamed response carried no id: {r}"
        status, rec = _get_json(gw, f"/v1/decisions/{rid}")
        assert status == 200, \
            f"{name}: {rid} has no decision record ({status})"
        assert "candidates" in rec and "attempts" in rec, rec
        oc = rec.get("outcome")
        assert oc, f"{name}: decision {rec.get('id')} never committed"
        assert oc["result"] in ("hit", "partial", "local"), oc
        assert oc["realized_total_s"] >= 0.0, oc
        assert oc["regret_s"] >= 0.0, oc
        assert "fallthroughs" in oc and "ttft_s" in oc, oc
        by_result[oc["result"]] = by_result.get(oc["result"], 0) + 1
        regret += oc["regret_s"]
        if oc.get("savings_vs_local_s") is not None:
            savings += oc["savings_vs_local_s"]
        checked += 1
    assert checked == sum(1 for r in results if r.get("status") == 200)
    return {"resolved": checked, "by_result": by_result,
            "regret_s": regret, "ttft_savings_vs_local_s": savings}


# ---------------------------------------------------------------------------
# acceptance: token identity vs a direct in-process scheduler run
# ---------------------------------------------------------------------------

def direct_tokens(model, params, tok, requests):
    """Greedy reference completions, no gateway, no cache."""
    eng = BatchedEngine(model, params, max_len=MAX_LEN, batch_size=2)
    sched = Scheduler(eng)
    reqs = []
    for wl in requests:
        segs = protocol.tokenize_messages(tok, wl.messages)
        reqs.append(Request(tokens=np.asarray(segs.token_ids, np.int32),
                            max_new_tokens=wl.max_new_tokens))
    sched.run(reqs)
    return [r.stats.output_tokens for r in reqs]


# ---------------------------------------------------------------------------
# acceptance: bounded shedding under slot exhaustion
# ---------------------------------------------------------------------------

def shed_drill(model, params, burst: int = 6) -> dict:
    """Burst a 1-slot gateway (queue_depth=1): extras must shed with
    429/503 + Retry-After, never queue unboundedly."""
    gw = Gateway(model, params, fabric=None, batch_size=1,
                 max_len=MAX_LEN, max_inflight=1, queue_depth=1,
                 default_quota=TenantQuota(max_concurrent=burst),
                 model_name="shed-drill").start()
    try:
        wls = MIXES["support"](burst, seed=7, rate_per_s=0.0,
                               max_new_tokens=48)
        results, wall = replay(gw, wls)
    finally:
        gw.stop()
    statuses = [r.get("status") for r in results]
    shed = [r for r in results if r.get("status") in (429, 503)]
    ok = [r for r in results if r.get("status") == 200]
    assert not any("error" in r for r in results), \
        f"shed drill had transport errors: {results}"
    assert all(s in (200, 429, 503) for s in statuses), \
        f"unexpected statuses under overload: {statuses}"
    assert shed, "slot exhaustion did not shed any requests"
    assert all(r.get("retry_after") for r in shed), \
        "shed responses missing Retry-After"
    assert ok, "overloaded gateway served nothing at all"
    return {"burst": burst, "served": len(ok), "shed": len(shed),
            "statuses": sorted(set(statuses)), "wall_s": wall}


# ---------------------------------------------------------------------------
# acceptance: silent-congestion drill -> estimator-drift alarm
# ---------------------------------------------------------------------------

def console_snapshot(gw) -> str:
    """``python -m repro.obs.console --once`` against the live gateway
    (real subprocess — the CI smoke path and the README screenshot)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs.console", "--once",
         "--gateway", f"{gw.server.host}:{gw.port}"],
        capture_output=True, text=True, timeout=60, env=env)
    assert proc.returncode == 0, proc.stderr
    assert "fleet console" in proc.stdout, proc.stdout
    return proc.stdout


def congestion_drill(model, params, n: int = 16) -> dict:
    """Silently degrade live peers and watch the drift alarm fire.

    Phase 1 seeds every prompt's ranges into the fleet, then the
    warm gateway retires (its broker blob cache would satisfy phase-2
    refetches without touching the wire). ``set_throttle`` then paces
    each daemon's serving socket — no restart, nothing announced to
    clients — and a fresh gateway replays the same prompts: every
    resolve refetches over a degraded link, est-vs-actual error blows
    past the calibration band, the ``repro_estimator_drift`` gauge
    flips, and the flight recorder dumps an ``estimator_drift``
    snapshot."""
    from repro.obs import REGISTRY
    from repro.obs.flight import ESTIMATOR_DRIFT

    # several seeds => several distinct hot system prefixes: the broker
    # dedups each unique blob to ONE wire transfer, so one seed's worth
    # of traffic gives each peer too few est-vs-actual samples to clear
    # the calibration tracker's min_obs gate
    wls = [w for s in range(4)
           for w in MIXES["support"](max(n // 4, 2), seed=11 + s,
                                     rate_per_s=0.0, max_new_tokens=4)]

    def mk(fabric, name):
        return Gateway(model, params, fabric=fabric, batch_size=4,
                       max_len=MAX_LEN, max_inflight=64, queue_depth=64,
                       default_quota=TenantQuota(max_concurrent=64),
                       model_name=name).start()

    with Fabric.tcp(n_peers=2, cache_cfg=CacheConfig()) as fabric:
        gw = mk(fabric, "congestion-warm")
        try:
            results, _ = replay(gw, wls, time_scale=0.0)
            assert all(r.get("status") == 200 for r in results), results
            gw.engine.fetcher.flush_uploads()
            up = gw.engine.fetcher.stats
            blob_b = up["bytes_up"] / max(up["uploads"], 1)
        finally:
            gw.stop()
        # pace so one blob transfer takes ~0.5s beyond the pacer's
        # ~0.2s burst credit; planner estimates still assume the
        # unthrottled link, so actuals blow past them
        bps = max(blob_b * 8.0 / 0.7, 5e4)
        for pid in fabric.peer_ids():
            r = fabric.supervisor.set_throttle(pid, bps)
            assert r.get("ok"), r

        gw = mk(fabric, "congestion-drill")
        try:
            results, _ = replay(gw, wls, time_scale=0.0)
            assert all(r.get("status") == 200 for r in results), results
            cal = gw.engine.fetcher.directory.calibration
            drifted = cal.drifted()
            snap = cal.snapshot()
            _, flight = _get_json(gw, "/v1/flight")
            dumps = [d for d in flight["dumps"]
                     if d.get("reason") == ESTIMATOR_DRIFT]
            gauge = REGISTRY.snapshot().get("repro_estimator_drift", {})
            console = console_snapshot(gw)
            fstats = dict(gw.engine.fetcher.stats)
        finally:
            gw.stop()
    assert fstats["hits"] > 0, f"drill refetched nothing: {fstats}"
    assert drifted, f"throttled fleet flagged no drift: {snap}"
    assert isinstance(gauge, dict) and any(gauge.values()), \
        f"repro_estimator_drift gauge never flipped: {gauge}"
    assert dumps, "no estimator_drift flight dump"
    return {"throttle_bps": bps, "drifted_peers": drifted,
            "drift_gauge": gauge, "n_drift_dumps": len(dumps),
            "refetch_hits": fstats["hits"], "calibration": snap,
            "console_once": console}


# ---------------------------------------------------------------------------

def _pct(vals, q):
    return float(np.percentile(vals, q)) if vals else 0.0


def run_mix(gw, model, params, tok, name: str, n: int, rate: float,
            seed: int = 0) -> dict:
    wls = MIXES[name](n, seed=seed, rate_per_s=rate,
                      max_new_tokens=MAX_NEW)
    # warmup: one request per distinct prefill bucket, off the clock
    # (compile stalls would otherwise land in the first TTFTs)
    seen, warm = set(), []
    for wl in wls:
        b = len(protocol.tokenize_messages(tok, wl.messages).token_ids)
        b = 1 << (b - 1).bit_length()
        if b not in seen:
            seen.add(b)
            warm.append(wl)
    replay(gw, warm, time_scale=0.0)

    results, wall = replay(gw, wls)
    errors = [r for r in results if "error" in r or "tokens" not in r]
    assert not errors, f"{name}: replay failures: {errors[:3]}"

    ref = direct_tokens(model, params, tok, wls)
    for i, (r, expect) in enumerate(zip(results, ref)):
        assert r["tokens"] == list(expect), (
            f"{name}: request {i} diverged from the direct scheduler "
            f"run: gateway={r['tokens']} direct={list(expect)}")

    ledger = resolve_decisions(gw, results, name)

    ttfts = [r["ttft_s"] for r in results]
    ttlts = [r["ttlt_s"] for r in results]
    shed_n = sum(1 for r in results if r.get("status") in (429, 503))
    fleet = len(gw.engine.fabric.peer_ids()) + 1    # peers + gateway box
    fstats = dict(gw.engine.fetcher.stats)
    gb = (fstats["bytes_down"] + fstats["bytes_up"]) / 1e9
    cost_1k = (wall / 3600 * fleet * DEVICE_USD_PER_HR
               + gb * EGRESS_USD_PER_GB) / max(n, 1) * 1000
    return {
        "n_requests": n, "wall_s": wall,
        "ttft_p50_s": _pct(ttfts, 50), "ttft_p95_s": _pct(ttfts, 95),
        "ttlt_p50_s": _pct(ttlts, 50), "ttlt_p95_s": _pct(ttlts, 95),
        "shed_rate": shed_n / max(n, 1),
        "cost_per_1k_usd": cost_1k,
        "cache": fstats,
        "ledger": ledger,
        "token_identity": "ok",
    }


def main(quick: bool = False, only_mix: str = ""):
    enable_compile_cache()
    cfg = get_config("gemma3-270m").reduced()
    model = Model(cfg)
    import jax
    params = model.init(jax.random.PRNGKey(0))
    tok = WordHashTokenizer(cfg.vocab)

    n = 6 if quick else 16
    rate = 12.0
    report = {"config": {"model": cfg.name, "max_len": MAX_LEN,
                         "max_new": MAX_NEW, "n_per_mix": n,
                         "rate_per_s": rate}, "mixes": {}}
    lines = []
    spans: dict = {}
    last_spans: list = []
    mixes = [only_mix] if only_mix else list(MIXES)
    for name in mixes:
        # fresh fleet per mix so cache stats and cost are per-mix
        with Fabric.tcp(n_peers=2, cache_cfg=CacheConfig()) as fabric:
            gw = Gateway(model, params, fabric=fabric, batch_size=4,
                         max_len=MAX_LEN, max_inflight=64,
                         queue_depth=64,
                         default_quota=TenantQuota(max_concurrent=64),
                         model_name=f"gateway-{name}").start()
            try:
                res = run_mix(gw, model, params, tok, name, n, rate)
            finally:
                # each mix owns a short-lived gateway; fold its span
                # rollup into the report before the tracer goes away
                merge_rollups(spans, gw.tracer.rollup())
                last_spans = gw.tracer.spans()
                gw.stop()
        report["mixes"][name] = res
        lines.append(csv_line(
            f"gateway_{name}", res["ttft_p50_s"] * 1e6,
            f"ttft_p95_ms={res['ttft_p95_s'] * 1e3:.1f};"
            f"ttlt_p95_ms={res['ttlt_p95_s'] * 1e3:.1f};"
            f"shed_rate={res['shed_rate']:.2f};"
            f"hits={res['cache']['hits']}/{res['cache']['resolves']};"
            f"regret_s={res['ledger']['regret_s']:.3f};"
            f"cost_1k=${res['cost_per_1k_usd']:.4f}"))

    report["shed_drill"] = shed_drill(model, params)
    lines.append(csv_line(
        "gateway_shed_drill", report["shed_drill"]["wall_s"] * 1e6,
        f"served={report['shed_drill']['served']};"
        f"shed={report['shed_drill']['shed']};"
        f"statuses={report['shed_drill']['statuses']}"))

    report["congestion_drill"] = congestion_drill(model, params)
    lines.append(csv_line(
        "gateway_congestion_drill",
        report["congestion_drill"]["throttle_bps"],
        f"drifted={report['congestion_drill']['drifted_peers']};"
        f"dumps={report['congestion_drill']['n_drift_dumps']}"))

    # whole-run ledger accounting + CI artifact spills: the full
    # decision ledger as JSONL, and the last mix's span tree as a
    # Perfetto-loadable trace
    from repro.obs import LEDGER
    from repro.obs.export import write_perfetto
    report["ledger_totals"] = LEDGER.totals()
    LEDGER.dump_jsonl("BENCH_gateway_load_ledger.jsonl")
    if last_spans:
        write_perfetto("BENCH_gateway_load_trace.json", last_spans,
                       default_proc="gateway")

    write_bench("BENCH_gateway_load.json", report, spans=spans)
    return lines


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI smoke sizes")
    ap.add_argument("--mix", default="", choices=["", *MIXES],
                    help="run a single mix")
    args = ap.parse_args()
    main(quick=args.quick, only_mix=args.mix)
