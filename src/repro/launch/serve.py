"""Serving launcher: run the cache server, or an edge client, over TCP.

  # terminal 1 — the "cache box"
  PYTHONPATH=src python -m repro.launch.serve server --port 7077

  # terminal 2..N — edge clients working an MMLU stream
  PYTHONPATH=src python -m repro.launch.serve client --port 7077 \
      --arch gemma3-270m --prompts 10 [--reduced]
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.config import CacheConfig
from repro.configs import get_config
from repro.core import CacheServer, EdgeClient
from repro.core.perfmodel import PI_ZERO_2W
from repro.core.transport import TCPTransport, serve_tcp
from repro.data import MMLUGenerator, WordHashTokenizer, MMLU_DOMAINS
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serving.engine import InferenceEngine


def run_server(args):
    server = CacheServer(CacheConfig())
    port, shutdown = serve_tcp(server, host=args.host, port=args.port)
    print(f"cache server on tcp://{args.host}:{port} (ctrl-c to stop)")
    try:
        while True:
            time.sleep(5)
            s = server.handle("stats", {})
            print(f"  entries={s['n_entries']} "
                  f"stored={s['stored_bytes'] / 1e6:.1f}MB {s['stats']}")
    except KeyboardInterrupt:
        shutdown()


def run_client(args):
    enable_compile_cache()
    cfg = get_config(args.arch)
    exec_cfg = cfg.reduced() if args.reduced else cfg
    model = Model(exec_cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    eng = InferenceEngine(model, params, max_len=1024)
    tr = TCPTransport(args.host, args.port)
    client = EdgeClient(f"client-{args.seed}", eng, tr, CacheConfig(),
                        perf=PI_ZERO_2W, perf_cfg=cfg)
    tok = WordHashTokenizer(exec_cfg.vocab)
    gen = MMLUGenerator(tok, n_shot=args.n_shot)
    for p in gen.stream(args.prompts, MMLU_DOMAINS[:args.domains]):
        client.sync_catalog()
        client.catalog.last_sync_t = -1e18
        r = client.infer(p.segments, max_new_tokens=args.max_new)
        print(f"{p.domain:28s} case={r.case} "
              f"matched={r.matched_tokens}/{r.prompt_tokens} "
              f"wall TTFT={(r.wall.ttft) * 1e3:7.1f}ms "
              f"redis={r.wall.redis * 1e3:6.1f}ms")
    tr.close()


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("server")
    s.add_argument("--host", default="127.0.0.1")
    s.add_argument("--port", type=int, default=7077)
    c = sub.add_parser("client")
    c.add_argument("--host", default="127.0.0.1")
    c.add_argument("--port", type=int, default=7077)
    c.add_argument("--arch", default="gemma3-270m")
    c.add_argument("--reduced", action="store_true",
                   help="run the 2-layer CPU-sized variant of --arch")
    c.add_argument("--prompts", type=int, default=10)
    c.add_argument("--domains", type=int, default=3)
    c.add_argument("--n-shot", type=int, default=2)
    c.add_argument("--max-new", type=int, default=8)
    c.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.cmd == "server":
        run_server(args)
    else:
        run_client(args)


if __name__ == "__main__":
    main()
