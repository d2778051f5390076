"""One cell, one run: build the served path, warm it, drive the window,
check the served tokens against the reference, report.

Everything cell-specific is read from files found by name:
``BENCHMARK.json`` names the cell's configuration and traffic;
``bench/configs/<config>.json`` holds the sizes and names the model
family, ``bench/families/<family>.py`` its weights, reference and work
counts, ``bench/traffic/<traffic>.json`` the mix,
``bench/cells/<cell>.json`` the offered rate and the correctness limit,
``bench/metrics/<metric>.py`` one reader per per-layer metric and
``bench/probes/*.py`` the traced calls. Nothing here names a
configuration, a family, a mix or a metric.
"""
from __future__ import annotations

import functools
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FAMILIES = BENCH / "families"
CACHE_DIR = ROOT / ".bench_cache" / "jax"

COMPILES: List[tuple] = []           # (perf_counter, program name)
_LISTENING: List[bool] = []


def _on_compile(event: str, duration: float, **kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILES.append((time.perf_counter(), kw.get("fun_name", "?")))


def setup_jax():
    """Persistent compile cache at a fixed path inside the checkout;
    every program is cached so only a cell's first run compiles."""
    import jax
    if not _LISTENING:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_compilation_cache_max_size", -1)
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _LISTENING.append(True)
    return jax


def require_chips(jax, chips: int) -> Optional[str]:
    """None when JAX sees an accelerator with enough chips."""
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        return f"no accelerator: JAX found platform {devs[0].platform!r}"
    if len(devs) < chips:
        return f"the cell needs {chips} chips, JAX found {len(devs)}"
    return None


def load_json(p: Path) -> dict:
    return json.loads(Path(p).read_text())


@functools.cache
def _load_module(f: Path, name: str):
    """One module per file and process, so a family's jitted functions
    and their compiled programs are shared by all who load it."""
    spec = importlib.util.spec_from_file_location(name, f)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    return _load_module(BENCH / "metrics" / f"{name}.py",
                        f"bench_metric_{name}").read


def load_family(config: dict, where: str):
    """The module ``bench/families/<family>.py`` that the configuration
    names under ``"family"`` (its interface: PERF.md, section 3).
    ``where`` names the configuration in the error raised when the key
    or the file is missing."""
    present = sorted(f.stem for f in FAMILIES.glob("*.py"))
    name = config.get("family")
    if name not in present:
        raise ValueError(
            f"{where}: " + ("no \"family\" key" if name is None else
                            f"family {name!r} has no file in {FAMILIES}")
            + f"; families present: {', '.join(present) or 'none'}")
    return _load_module(FAMILIES / f"{name}.py", f"bench_family_{name}")


class CellSpec:
    """The data files of one cell."""

    def __init__(self, bench: dict, workload: str):
        w = {x["name"]: x for x in bench["workloads"]}[workload]
        file = {x["name"]: x for x in bench["configs"]}[w["config"]]["file"]
        self.bench = bench
        self.name = workload
        self.chips = int(w["chips"])
        self.config = load_json(ROOT / file)
        self.family = load_family(self.config, file)
        self.mix = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
        self.cell = load_json(BENCH / "cells" / f"{workload}.json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in bench["per_layer"]
                          if workload in m.get("workloads", [workload])]

    @classmethod
    def of(cls, name: str, config: dict, mix: dict, cell: dict,
           end_to_end=(), per_layer=(), chips: int = 1) -> "CellSpec":
        """A cell from data in hand (tests, sweeps)."""
        self = cls.__new__(cls)
        self.bench, self.name, self.chips = None, name, chips
        self.config, self.mix, self.cell = config, mix, cell
        self.family = load_family(config, f"the configuration of {name}")
        self.end_to_end, self.per_layer = list(end_to_end), list(per_layer)
        return self


def say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class Served:
    """The program under test, built from a config file, its family's
    module and a seed."""

    def __init__(self, config: dict, family, seed: int,
                 tracer_traces: int = 0):
        import jax
        import jax.numpy as jnp
        from bench.lib.common import base_key, check_layout
        sys.path.insert(0, str(ROOT / "src"))
        from repro.configs import get_config
        from repro.core import Fabric
        from repro.gateway import Gateway, TenantQuota
        from repro.models import Model
        from repro.obs.trace import Tracer

        self.parts: Dict[str, float] = {}
        t = time.perf_counter()
        self.config = config
        self.shape = family.Shape(config)
        cfg = family.program_config(config, get_config(config["registry"]))
        self.model = Model(cfg, dtype=jnp.bfloat16)
        key = jnp.asarray(base_key(seed))
        params = family.make_params_jit(self.shape, key)
        diffs = check_layout(
            params, jax.eval_shape(self.model.init, jax.random.PRNGKey(0)))
        if diffs:
            raise RuntimeError("weights do not fit the program's layout: "
                               + "; ".join(diffs[:5]))
        jax.block_until_ready(params)
        self.parts["weights"] = time.perf_counter() - t

        t = time.perf_counter()
        self.fabric = Fabric.tcp(n_peers=int(config.get("peers", 2)))
        self.fabric.start()
        self.parts["peers"] = time.perf_counter() - t
        t = time.perf_counter()
        try:
            self.gw = Gateway(
                self.model, params, fabric=self.fabric,
                batch_size=int(config["batch_size"]),
                max_len=int(config["max_len"]),
                model_name=config["registry"],
                request_timeout_s=600.0,
                queue_depth=1 << 20,
                default_quota=TenantQuota(max_concurrent=1 << 20),
                tracer=(Tracer(proc="gateway", max_traces=tracer_traces)
                        if tracer_traces else None)).start(600.0)
        except BaseException:
            self.fabric.stop()
            raise
        del params
        self.parts["gateway"] = time.perf_counter() - t

    @property
    def port(self) -> int:
        return self.gw.port

    def idle(self, timeout_s: float = 600.0) -> None:
        eng = self.gw.engine
        end = time.perf_counter() + timeout_s
        while time.perf_counter() < end:
            if (eng.inbox.empty() and not eng.sched.has_work
                    and self.gw.admission.inflight == 0):
                return
            time.sleep(0.01)
        raise TimeoutError("the gateway did not go idle")

    def settle(self) -> None:
        """Uploads flushed, then one catalog sync period."""
        f = self.gw.engine.fetcher
        if not f.flush_uploads(600.0):
            raise TimeoutError("uploads did not drain")
        time.sleep(f.directory.cache_cfg.sync_interval_s + 0.2)

    def warm_resume(self, lengths) -> None:
        """Compile the exact-length resume prefill for each suffix length
        directly on the idle engine (slot 0, junk state, then freed)."""
        eng = self.gw.engine.engine
        m = eng.model
        for n in sorted(set(lengths)):
            c1 = m.init_cache(1, m.cache_len(eng.max_len), eng.cache_dtype)
            eng.prefill_slot(0, np.full(n, 100, np.int32), c1, 1)
            eng.free_slot(0)

    def warm_fresh(self, buckets) -> None:
        """Compile the batched fresh prefill at each bucket (its batch is
        always padded to every slot) and the row scatter for each count
        of admitted rows (its shapes hold no bucket), on the idle
        engine."""
        eng = self.gw.engine.engine
        buckets = sorted(set(buckets))
        for b, k in ([(b, 1) for b in buckets] + [
                (buckets[0], k) for k in range(2, eng.batch_size + 1)]):
            eng.prefill_slots(list(range(k)),
                              [np.full(b, 100, np.int32)] * k)
            for s in range(k):
                eng.free_slot(s)

    def requests_by_prompt(self) -> Dict[tuple, tuple]:
        """prompt ids -> (matched tokens, prompt tokens) of every request
        the engine finished."""
        out = {}
        for r in self.gw.engine.sched.done:
            out[tuple(int(x) for x in r.tokens)] = (
                int(r.n_prefix), int(r.stats.prompt_tokens))
        return out

    def spans(self) -> List[dict]:
        return self.gw.tracer.spans()

    def stop(self) -> None:
        try:
            self.gw.stop()
        finally:
            self.fabric.stop()
        self.gw = None
        self.model = None
        gc.collect()


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def bodies(reqs, model_name: str) -> List[bytes]:
    return [json.dumps(r.body(model_name)).encode() for r in reqs]


def fire(served: Served, reqs, dues, deadline, on_window_end=None,
         window_end=0.0):
    from bench.lib import client
    return client.replay("127.0.0.1", served.port,
                         bodies(reqs, served.config["registry"]),
                         dues, deadline, on_window_end, window_end)


def warm(served: Served, spec: CellSpec, seed: int, reqs) -> None:
    """Every shape the window's schedule uses, and the cache fill."""
    from bench.lib import traffic as tr
    from bench.lib.textproto import chat_ids
    mix, vocab = spec.mix, served.shape.vocab
    bs = int(served.config["batch_size"])
    role = (mix.get("shared_prompts") or {}).get("role", "system")

    def batch(msgs_list, max_tokens=2):
        now = time.perf_counter()
        warm_reqs = [tr.Request(0.0, m, max_tokens, tenant="warm")
                     for m in msgs_list]
        outs = fire(served, warm_reqs, [now] * len(warm_reqs), now + 600.0)
        bad = [o.error for o in outs if o.error or o.done is None]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0]}")
        served.idle()

    t = time.perf_counter()
    shared = tr.shared_prompts(mix, seed)
    if shared:
        # the fill: each shared prompt once as a miss, uploaded to peers
        batch([((role, p), ("user", f"warm {j} fill")) for j, p in
               enumerate(shared)])
        served.settle()
    served.parts["cache_fill"] = time.perf_counter() - t

    t = time.perf_counter()
    if shared:
        # one partial hit per shared prompt: fetch, parse and restore
        # shapes of each prefix length
        batch([((role, p), ("user", f"warm {j} hit")) for j, p in
               enumerate(shared)])
        served.warm_resume(len(chat_ids(r.messages, vocab))
                           - len(chat_ids(r.messages[:1], vocab))
                           for r in reqs if r.prompt_id is not None)
    # fresh-prefill buckets of the window's misses, every slot in use
    fresh = {}
    for r in reqs:
        if r.prompt_id is None:
            n = len(chat_ids(r.messages, vocab))
            b = min(1 << max(n - 1, 15).bit_length(),
                    int(served.config["max_len"]))
            fresh.setdefault(b, r.messages)
    if fresh:
        served.warm_fresh(fresh)
        # the gateway's own path once, every slot in use: uploads,
        # decode, sampling
        msgs = fresh[max(fresh)]
        head = msgs[-1][1].rsplit(" ", 1)[0]
        batch([msgs[:-1] + (("user", f"{head} warm{i}"),)
               for i in range(bs)])
    served.settle()
    served.idle()
    served.parts["warm"] = time.perf_counter() - t


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class Window:
    """What one measured window produced."""

    def __init__(self, reqs, outs, t0, t1, deadline):
        self.reqs, self.outs = reqs, outs
        self.t0, self.t1, self.deadline = t0, t1, deadline

    def ok(self, i: int) -> bool:
        o, r = self.outs[i], self.reqs[i]
        return (o.status == 200 and o.error is None and o.done is not None
                and len(o.tokens) == r.max_tokens)

    @property
    def failed(self) -> int:
        return sum(not self.ok(i) for i in range(len(self.outs)))

    def latency(self, i: int, which: str) -> float:
        o = self.outs[i]
        if not self.ok(i):
            return self.deadline - o.due        # never came: at the top
        return (o.first if which == "first" else o.done) - o.due


def run_window(served: Served, reqs, seconds: float, drain_s: float,
               trace_s: float = 0.0) -> Window:
    """Drive one window. ``trace_s`` > 0: a profiler trace is running;
    mark the window's first ``trace_s`` seconds in it and stop it then,
    while the window goes on (the profiler's stop releases the GIL)."""
    import jax
    lead = 0.2
    t0 = time.perf_counter() + lead
    dues = [t0 + r.due_s for r in reqs]
    t1 = t0 + seconds
    deadline = t1 + drain_s
    ann = None
    if trace_s > 0:
        ann = jax.profiler.TraceAnnotation("bench.window")
        time.sleep(max(t0 - time.perf_counter(), 0.0))
        ann.__enter__()

    def close():
        if ann is not None:
            ann.__exit__(None, None, None)
            t = time.perf_counter()
            jax.profiler.stop_trace()
            say(f"profiler stopped in {time.perf_counter() - t:.3f} s")

    outs = fire(served, reqs, dues, deadline, close if ann else None,
                t0 + min(trace_s, seconds))
    win = Window(reqs, outs, t0, t1, deadline)
    stuck = [i for i, o in enumerate(outs) if o.done is None]
    if stuck:
        # what the engine thread was doing when the drain gave up
        import faulthandler
        say(json.dumps({"unfinished": [
            {"due_s": reqs[i].due_s, "prompt_id": reqs[i].prompt_id,
             "status": outs[i].status, "tokens": len(outs[i].tokens),
             "of": reqs[i].max_tokens, "error": outs[i].error}
            for i in stuck[:8]]}))
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    return win


def end_to_end(win: Window, setup_s: float) -> Dict[str, float]:
    from bench.lib.stats import nearest_rank
    n = len(win.outs)
    first = [win.latency(i, "first") for i in range(n)]
    last = [win.latency(i, "done") for i in range(n)]
    gap_t = sum(win.outs[i].done - win.outs[i].first for i in range(n)
                if win.ok(i) and len(win.outs[i].tokens) > 1)
    gap_n = sum(len(win.outs[i].tokens) - 1 for i in range(n)
                if win.ok(i) and len(win.outs[i].tokens) > 1)
    return {"ttft_p50_s": nearest_rank(first, 50),
            "ttft_p90_s": nearest_rank(first, 90),
            "ttlt_p90_s": nearest_rank(last, 90),
            "itl_mean_s": gap_t / gap_n if gap_n else None,
            "setup_s": setup_s}


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample_finished(win: Window, seed: int, k: int) -> List[int]:
    """The finished request with most served tokens, and k-1 others
    drawn from the seed."""
    ok = [i for i in range(len(win.outs)) if win.ok(i)]
    if not ok:
        return []
    longest = max(ok, key=lambda i: (len(win.outs[i].tokens), -i))
    rest = [i for i in ok if i != longest]
    rng = np.random.default_rng([seed, 2])
    pick = list(rng.choice(rest, size=min(k - 1, len(rest)),
                           replace=False)) if rest else []
    return [longest] + sorted(int(i) for i in pick)


def reference_inputs(win: Window, picked: List[int], vocab: int,
                     max_len: int, k: int):
    """Token block [k, max_len] (prompt + served tokens, zero padded,
    short samples repeated to keep one shape) and the (row, position,
    served token) of every served token."""
    from bench.lib.textproto import chat_ids
    toks = np.zeros((k, max_len), np.int32)
    rows = []
    for b, i in enumerate(picked):
        p = chat_ids(win.reqs[i].messages, vocab)
        served = win.outs[i].tokens
        seq = p + served[:-1]
        toks[b, :len(seq)] = seq
        rows.extend((b, len(p) - 1 + j, t) for j, t in enumerate(served))
    for b in range(len(picked), k):
        toks[b] = toks[0]
    return toks, rows


def judge(gap: float, served: int, failed: int, limit: float):
    """``correct`` and the numbers it compares, each beside its limit:
    the widest logit gap of a served token, the served tokens compared
    (at least one) and the requests due in the window that failed or
    never finished within the drain (none)."""
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "served_tokens_compared": {"value": served, "limit": 1},
              "failed_requests": {"value": failed, "limit": 0}}
    return served >= 1 and gap <= limit and failed <= 0, checks


def logit_gap(family, shape, seed: int, toks, rows, fp8: bool = False,
              row_cap: int = 0):
    """Widest gap by which a served token's reference logit (the
    family's ``Reference``) lies below the reference's best (and, with
    ``fp8``, the gaps of the tokens the fp8 control ranks first)."""
    from bench.lib.common import served_gaps
    ref = family.Reference(shape)
    pos = [(b, p) for b, p, _ in rows]
    lg = ref.logits_at(seed, toks, pos, row_cap)
    say(f"reference: block {list(toks.shape)}, first layer (with its "
        f"compile or cache load) {ref.first_layer_s:.3f} s")
    gaps = served_gaps(lg, [t for _, _, t in rows])
    out = {"program": float(gaps.max())}
    if fp8:
        c = family.Reference(shape, fp8=True).logits_at(seed, toks, pos,
                                                        row_cap)
        out["control_fp8"] = float(
            served_gaps(lg, c.argmax(axis=-1)).max())
    return out


# ---------------------------------------------------------------------------
# per-layer records
# ---------------------------------------------------------------------------

class Records:
    """What a per-layer metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def calls(self, name: str):
        return self.probes.within(name, self.t0, self.t1)


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(spec: CellSpec, seed: int, seconds: float, trace: bool,
        t_start: float, jax, control: bool = False) -> dict:
    """One run of the cell. With ``control`` the fp8 reference is also
    read on the same served tokens (``checks.control_fp8``); the
    benchmark's own runs never do that."""
    from bench.lib import traffic as tr
    from bench.lib.probes import Recorder, load_probe_files
    from bench.lib.textproto import chat_ids

    parts = {"process_and_imports": time.perf_counter() - t_start}
    rate = float(spec.cell["rate_per_s"])
    reqs = tr.schedule(spec.mix, seed, rate, seconds)
    served = Served(spec.config, spec.family, seed,
                    tracer_traces=(len(reqs) + 256) if trace else 0)
    stopped = False
    tdir = None
    try:
        parts.update(served.parts)
        if trace:
            # the trace starts before the warm-up, so what the profiler
            # pays on a program's first traced run falls in set-up
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            # device ops and TraceMe annotations only: the Python tracer
            # (on by default) records every call of every thread, slows
            # the host code it times and takes minutes to write out
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        warm(served, spec, seed, reqs)
        parts.update(served.parts)
        rec = None
        if trace:
            rec = Recorder().install(load_probe_files(BENCH / "probes"))
        n_compiles0 = len(COMPILES)
        setup_s = time.perf_counter() + 0.2 - t_start
        trace_s = min(float(spec.cell["trace_s"]), seconds) if trace \
            else 0.0
        win = run_window(served, reqs, seconds,
                         float(spec.cell["drain_s"]), trace_s)
        if rec is not None:
            rec.uninstall()
        late = sorted(o.late_s for o in win.outs)
        in_window = [c for c in COMPILES[n_compiles0:]
                     if win.t0 <= c[0] < win.t1]
        say(json.dumps({"setup_parts_s": parts, "setup_s": setup_s}))
        errors: Dict[str, int] = {}
        for i, o in enumerate(win.outs):
            if not win.ok(i):
                e = (o.error or f"status {o.status}, {len(o.tokens)} "
                     f"tokens")[:120]
                errors[e] = errors.get(e, 0) + 1
        say(json.dumps({
            "requests_due": len(reqs), "failed": win.failed,
            "errors": errors,
            "generator_late_s": {"median": late[len(late) // 2] if late
                                 else 0.0, "max": late[-1] if late
                                 else 0.0},
            "compiles_in_window": [c[1] for c in in_window],
            "first_ttft_s": [win.latency(i, "first")
                             for i in range(min(3, len(reqs)))],
            "slowest_ttft_s": sorted(
                ([win.latency(i, "first"), reqs[i].due_s]
                 for i in range(len(reqs))), reverse=True)[:3]}))
        device = device_info(jax, spec.chips)
        prog = served.requests_by_prompt()
        mismatch = 0
        window_prog = []
        for r in reqs:
            ids = tuple(chat_ids(r.messages, served.shape.vocab))
            got = prog.get(ids)
            if got is None:
                mismatch += 1
            else:
                window_prog.append(got)
        if mismatch:
            say(f"note: {mismatch} window prompts not found among the "
                f"engine's finished requests under the benchmark's own "
                f"tokenization")
        spans = served.spans() if trace else []
        served.stop()
        stopped = True

        metrics: Dict[str, dict] = {}
        breakdown = None
        if trace:
            from bench.lib import trace as trmod
            import glob
            files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                              recursive=True)
            t = time.perf_counter()
            red = trmod.reduce(trmod.read_xplane(files[0]))
            say(f"trace read in {time.perf_counter() - t:.3f} s; "
                f"programs: " + json.dumps(
                    {k: [len(v), sum(v)] for k, v in
                     sorted(red["programs"].items(),
                            key=lambda kv: -sum(kv[1]))[:8]}))
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = red["breakdown"]
            # every per-layer metric reads the traced span
            records = Records(
                t0=win.t0, t1=win.t0 + trace_s, probes=rec, trace=red,
                compiles_in_window=len(in_window), spans=spans,
                window_requests=window_prog, shape=served.shape,
                work=lambda live: spec.family.decode_work(served.shape,
                                                          live),
                peaks=peaks_for(device["kind"]))
            for m in spec.per_layer:
                v = load_reader(m["name"])(records)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
            import shutil
            shutil.rmtree(tdir, ignore_errors=True)
        else:
            e2e = end_to_end(win, setup_s)
            for m in spec.end_to_end:
                v = e2e.get(m["name"])
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}

        k = int(spec.cell["check_requests"])
        picked = sample_finished(win, seed, k)
        limit = float(spec.cell["logit_gap_limit"])
        t = time.perf_counter()
        if picked:
            pad = min(int(spec.config["max_len"]),
                      -(-tr.longest(spec.mix) // 128) * 128)
            toks, rows = reference_inputs(win, picked, served.shape.vocab,
                                          pad, k)
            gaps = logit_gap(spec.family, served.shape, seed, toks, rows,
                             fp8=control,
                             row_cap=k * spec.mix["output_tokens"]["hi"])
            gap = gaps["program"]
        else:
            rows, gap, gaps = [], float("nan"), {}
        say(f"reference over {len(picked)} requests, {len(rows)} served "
            f"tokens: {time.perf_counter() - t:.3f} s")
        correct, checks = judge(gap, len(rows), win.failed, limit)
        if "control_fp8" in gaps:
            checks["control_fp8_gap"] = {"value": gaps["control_fp8"],
                                         "limit": limit}
        out = {"correct": correct,
               "attempted": len(reqs), "failed": win.failed,
               "metrics": metrics, "device": device}
        if breakdown is not None:
            out["breakdown"] = breakdown
        out["checks"] = checks
        for name, c in checks.items():
            say(f"check {name}: {c['value']} limit {c['limit']}")
        return out
    finally:
        if not stopped:
            served.stop()
