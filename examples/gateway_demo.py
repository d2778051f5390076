"""The HTTP front door, end to end on one machine.

Stands up a real TCP peer fleet (``Fabric.tcp``), puts the
OpenAI-compatible gateway in front of it, and replays a short
customer-support mix over plain ``http.client`` — the same calls any
OpenAI SDK or ``curl`` would make:

    curl -s localhost:PORT/v1/chat/completions -d '{
      "messages": [{"role": "user", "content": "hello"}],
      "max_tokens": 8, "user": "tenant-a"}'

Shows: cold-miss upload, warm prefix hits served by peers, SSE
streaming, per-tenant accounting, and a 429 when a tenant bursts past
its quota.

    PYTHONPATH=src python examples/gateway_demo.py [--local]
"""
import argparse
import http.client
import json

import jax

from repro.config import CacheConfig
from repro.configs import get_config
from repro.core import Fabric
from repro.gateway import Gateway, TenantQuota
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.workloads import customer_support


def post(port, path, body, stream=False):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp, data


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--local", action="store_true",
                    help="single in-process cache box instead of the "
                         "TCP peer fleet")
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config("gemma3-270m").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    fabric = (Fabric.local() if args.local
              else Fabric.tcp(n_peers=2, cache_cfg=CacheConfig()).start())
    print(f"fabric: {fabric!r}")
    gw = Gateway(model, params, fabric=fabric, batch_size=4,
                 max_len=384,
                 quotas={"bursty": TenantQuota(max_concurrent=8,
                                               rate_per_s=0.001,
                                               burst=2)}).start()
    print(f"gateway: {gw.url}  (POST /v1/completions, "
          f"/v1/chat/completions)")

    for wl in customer_support(args.requests, seed=3, rate_per_s=0.0,
                               n_tenants=2):
        resp, data = post(gw.port, "/v1/chat/completions", wl.body())
        out = json.loads(data)
        cache = out["cache"]
        print(f"  [{wl.tenant}] {resp.status} "
              f"matched={cache['matched_tokens']:3d} "
              f"via={cache['served_by'] or 'fresh':8s} "
              f"tokens={out['choices'][0]['token_ids']}")

    # SSE: same endpoint, stream=True
    conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=60)
    conn.request("POST", "/v1/completions",
                 json.dumps({"prompt": "stream a few tokens",
                             "max_tokens": 4, "stream": True}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    events = [e for e in resp.read().split(b"\n\n") if e]
    conn.close()
    print(f"  SSE: {len(events)} events, last = {events[-1].decode()}")

    # quota drill: tenant 'bursty' has a 2-request bucket
    statuses = [post(gw.port, "/v1/completions",
                     {"prompt": "over quota?", "max_tokens": 2,
                      "user": "bursty"})[0].status for _ in range(4)]
    print(f"  bursty tenant statuses: {statuses} (429 = shed)")

    rep = gw.report()
    print(f"\nreport: {rep.n_requests} served, "
          f"ttft_p50={rep.ttft_p50 * 1e3:.1f}ms, "
          f"shed={rep.shed_requests}")
    for t, ts in sorted(rep.per_tenant.items()):
        print(f"  tenant {t}: n={ts.n_requests} "
              f"ttft_p50={ts.ttft_p50 * 1e3:.1f}ms shed={ts.shed}")
    gw.stop()
    fabric.stop()
    print("gateway + fleet stopped")


if __name__ == "__main__":
    main()
