"""Shared benchmark world: reduced executable models emulating the paper's
full-size Gemma-3 settings through the device perf model + analytic blob
sizing (see core/sizing.py)."""
from __future__ import annotations

import json
import time
from dataclasses import dataclass

import jax

from repro.config import CacheConfig
from repro.configs import get_config
from repro.core import CacheServer, EdgeClient, SimClock, SimNetwork
from repro.core.perfmodel import PI_5, PI_ZERO_2W, TPU_V5E
from repro.core.transport import InProcTransport
from repro.data import MMLUGenerator, WordHashTokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serving.engine import InferenceEngine


@dataclass
class World:
    name: str
    cfg: object            # full-size config (perf emulation)
    exec_cfg: object       # reduced executable config
    model: object
    params: object
    server: CacheServer
    clock: SimClock
    net: SimNetwork
    gen: MMLUGenerator
    perf: object
    n_shot: int

    def client(self, name: str, **kw) -> EdgeClient:
        eng = InferenceEngine(self.model, self.params, max_len=1024)
        tr = InProcTransport(self.server, self.net, self.clock)
        return EdgeClient(name, eng, tr, CacheConfig(), perf=self.perf,
                          perf_cfg=self.cfg, **kw)


_CACHE = {}


def make_world(setting: str = "low") -> World:
    """'low' = Pi Zero 2W + Gemma-3 270M (N=1 shot);
    'high' = Pi 5 + Gemma-3 1B (N=5 shot);
    'tpu'  = v5e serving replica (beyond-paper)."""
    if setting in _CACHE:
        w = _CACHE[setting]
        w.server.__init__(CacheConfig())     # fresh server per bench
        w.clock.t = 0.0
        return w
    enable_compile_cache()
    full = {"low": "gemma3-270m", "high": "gemma3-1b",
            "tpu": "gemma3-1b"}[setting]
    perf = {"low": PI_ZERO_2W, "high": PI_5, "tpu": TPU_V5E}[setting]
    n_shot = 1 if setting == "low" else 5
    cfg = get_config(full)
    exec_cfg = cfg.replace(name=cfg.name + "-exec", n_layers=2,
                           d_model=128, n_heads=4, n_kv_heads=1,
                           head_dim=32, d_ff=256, vocab=4096)
    model = Model(exec_cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = WordHashTokenizer(exec_cfg.vocab)
    gen = MMLUGenerator(tok, n_shot=n_shot,
                        question_words=(24, 40), example_words=(24, 40))
    w = World(setting, cfg, exec_cfg, model, params, CacheServer(
        CacheConfig()), SimClock(), SimNetwork(), gen, perf, n_shot)
    _CACHE[setting] = w
    return w


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0)


def csv_line(name: str, us_per_call: float, derived: str) -> str:
    line = f"{name},{us_per_call:.1f},{derived}"
    print(line, flush=True)
    return line


def merge_rollups(into: dict, rollup: dict) -> dict:
    """Accumulate ``Tracer.rollup()`` dicts across benchmark stages
    (each stage may own a short-lived tracer)."""
    for name, agg in rollup.items():
        tot = into.setdefault(name, {"count": 0, "total_s": 0.0})
        tot["count"] += agg["count"]
        tot["total_s"] += agg["total_s"]
    return into


def write_bench(path: str, payload: dict, spans: dict = None) -> None:
    """Write a ``BENCH_*.json`` report with the run's observability
    state attached under ``"obs"``: the process-wide Prometheus metrics
    snapshot plus *spans*, a per-span-name rollup ({name: {count,
    total_s}}, see ``Tracer.rollup``) when the benchmark ran with
    tracing. Keeps every bench artifact self-describing — a regression
    report carries the phase breakdown that explains it."""
    from repro.obs import REGISTRY

    obs: dict = {"metrics": REGISTRY.snapshot()}
    if spans:
        obs["spans"] = spans
    payload = dict(payload)
    payload["obs"] = obs
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=str)
