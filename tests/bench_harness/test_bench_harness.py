"""Tests of the benchmark's yardstick, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness

Traffic generation, trace reduction, the required-work functions, the
peaks lookup, the probes, and the comparison that decides ``correct``:
the fp8 control, a token altered where it is produced and a request
that fails must each come out as not correct. No test here describes a
TPU topology.
"""
import json
import sys
import time
import warnings
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.lib import dense, harness, probes, trace, traffic  # noqa: E402
from bench.lib.textproto import chat_ids  # noqa: E402

MIXES = sorted((ROOT / "bench" / "traffic").glob("*.json"))
JAX_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                     "jax_persistent_cache_min_compile_time_secs",
                     "jax_persistent_cache_min_entry_size_bytes",
                     "jax_compilation_cache_max_size")


@pytest.fixture(scope="module", autouse=True)
def keep_jax_cache_config():
    """The harness points JAX's persistent cache at the benchmark's own
    directory; give the other tests of this process theirs back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = {k: getattr(jax.config, k) for k in JAX_CACHE_OPTIONS}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _load(p):
    return json.loads(Path(p).read_text())


def _lengths(reqs, vocab=151936):
    """The multisets of user-turn and of output lengths (each is fixed;
    their pairing, and which shared prompt goes with which, is drawn by
    the seed)."""
    return (sorted(len(chat_ids(r.messages[-1:], vocab)) for r in reqs),
            sorted(r.max_tokens for r in reqs))


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mix", MIXES, ids=lambda p: p.stem)
def test_one_seed_gives_one_schedule(mix):
    m = _load(mix)
    a = traffic.schedule(m, 2**31 + 11, 1.7, 30)
    b = traffic.schedule(m, 2**31 + 11, 1.7, 30)
    assert [(r.due_s, r.messages, r.max_tokens) for r in a] == \
        [(r.due_s, r.messages, r.max_tokens) for r in b]


@pytest.mark.parametrize("mix", MIXES, ids=lambda p: p.stem)
def test_seeds_share_composition_not_words(mix):
    m = _load(mix)
    a = traffic.schedule(m, 5, 2.3, 40)
    b = traffic.schedule(m, 3_000_000_017, 2.3, 40)
    assert len(a) == len(b) == round(2.3 * 40)
    assert _lengths(a) == _lengths(b)
    assert Counter(r.prompt_id for r in a) == \
        Counter(r.prompt_id for r in b)
    assert {r.messages[-1][1] for r in a}.isdisjoint(
        {r.messages[-1][1] for r in b})
    # one schedule for every seed: due times, lengths, prompt order
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [(r.max_tokens, r.prompt_id) for r in a] == \
        [(r.max_tokens, r.prompt_id) for r in b]
    assert all(0 <= r.due_s < 40 for r in a)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)


def test_stratified_arrivals_offer_every_seed_the_same_gaps():
    m = _load(ROOT / "bench" / "traffic" / "chat-short.json")
    n = round(0.8 * 51)
    due = np.array([r.due_s for r in traffic.schedule(m, 1, 0.8, 51)])
    assert 0 < due[0] and due[-1] < 51
    gaps = np.sort(np.diff(due, prepend=0.0))
    q = -np.log1p(-(np.arange(n) + 0.5) / n)         # Exp(1) quantiles
    assert np.allclose(gaps, 51 * q / (q.sum() + q.mean()))


@pytest.mark.parametrize("rate,seconds", [(0.8, 51), (2.5, 10), (3.3, 7)])
def test_count_is_rate_times_window(rate, seconds):
    for mix in MIXES:
        assert len(traffic.schedule(_load(mix), 1, rate, seconds)) == \
            round(rate * seconds)


def test_lengths_are_the_stated_quantiles():
    m = _load(ROOT / "bench" / "traffic" / "support-long.json")
    reqs = traffic.schedule(m, 9, 2.0, 50)
    shared = traffic.shared_prompts(m, 9)
    want = traffic.shared_prompt_lengths(m)
    vocab = 151936
    assert [len(chat_ids([("system", p)], vocab)) for p in shared] == want
    suffix = sorted(len(chat_ids(r.messages, vocab))
                    - len(chat_ids(r.messages[:1], vocab)) for r in reqs)
    assert suffix == traffic.quantiles(m["user_tokens"], len(reqs))
    counts = Counter(r.prompt_id for r in reqs)
    by_rank = traffic.zipf_counts(8, m["shared_prompts"]["zipf_a"],
                                  len(reqs))
    assert [counts[j] for j in range(8)] == \
        [by_rank[r] for r in m["shared_prompts"]["rank_of_prompt"]]


# ---------------------------------------------------------------------------
# trace reduction, required work, peaks
# ---------------------------------------------------------------------------

def _trace(ops, host, programs=()):
    return {"ops": {"/device:TPU:0": list(ops)},
            "programs": {"/device:TPU:0": list(programs)},
            "host": list(host)}


def test_busy_union_counts_overlap_once():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 2)]
    assert trace.busy_ns(ops, 0, 100) == 20
    assert trace.busy_ns(ops, 8, 32) == 9
    assert trace.gaps(ops, 0, 40) == [(15, 30), (35, 40)]


def test_gaps_named_by_the_enclosing_annotation():
    ops = [("op", 0, 10), ("op", 50, 10), ("op", 80, 10)]
    host = [("bench.window", 0, 100),
            ("bench.decode_step", 0, 70),      # outer
            ("bench.sample", 12, 30),          # inner, covers the gap
            ("bench.resolve", 62, 16)]
    red = trace.reduce(_trace(ops, host))
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(30e-9)
    names = dict((round(s * 1e9), n) for n, s in
                 red["breakdown"]["idle_gaps"])
    assert names == {40: "bench.sample", 20: "bench.resolve",
                     10: "unattributed"}


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99 imaginary")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_decode_roofline_is_100_when_device_time_is_the_bound():
    spec = _load(ROOT / "bench" / "configs" / "yi-6b.json")
    shape = dense.Shape(spec)
    pk = harness.peaks_for("TPU v5 lite")
    live = [300, 17, 512, 64]
    flops, nbytes = dense.decode_work(shape, live)
    bound = max(flops / pk["bf16_flops_per_s"],
                nbytes / pk["hbm_bytes_per_s"])
    # weight-bound: the layers and the head, 11.6 GB of bf16, dominate
    # (a step gathers only its rows of the input embedding)
    assert nbytes > 11.6e9 and bound == nbytes / pk["hbm_bytes_per_s"]
    read = harness.load_reader("decode_step_roofline")
    for device_s, want in [(bound, 100.0), (2 * bound, 50.0)]:
        rec = harness.Records(
            t0=0.0, t1=10.0, trace={"programs": {
                "jit__decode_one": [device_s] * 3}},
            probes=_FakeProbes({"bench.decode_step":
                                [(1.0, 2.0, live)] * 3}),
            work=lambda lv: dense.decode_work(shape, lv), peaks=pk)
        assert read(rec) == pytest.approx(want)


def test_decode_work_counts_weights_once_and_live_kv():
    shape = dense.Shape(_load(ROOT / "bench" / "configs" / "qwen3-4b.json"))
    assert dense.weight_params(shape) == pytest.approx(4.02e9, rel=0.01)
    f1, b1 = dense.decode_work(shape, [100])
    f2, b2 = dense.decode_work(shape, [100, 100])
    kv_token = 36 * 2 * 8 * 128 * 2
    assert b2 - b1 == pytest.approx(100 * kv_token + 151936 * 4)
    assert f2 == pytest.approx(2 * f1)


class _FakeProbes:
    def __init__(self, calls, missing=()):
        self.calls, self.missing = calls, set(missing)

    def within(self, name, lo, hi):
        if name in self.missing:
            return None
        return [c for c in self.calls.get(name, []) if lo <= c[0] < hi]


# ---------------------------------------------------------------------------
# probes
# ---------------------------------------------------------------------------

class _Target:
    def work(self, x, y=1):
        return x * 10 + y


class _Probe:
    def __init__(self, name, module, attr, capture=None):
        self.NAME, self.MODULE, self.ATTR = name, module, attr
        if capture:
            self.capture = capture


def test_wrapped_call_keeps_its_result():
    rec = probes.Recorder().install([
        _Probe("bench.work", __name__, "_Target.work",
               capture=lambda self, x, y=1: x)])
    try:
        assert _Target().work(4, y=2) == 42
    finally:
        rec.uninstall()
    assert _Target.work.__name__ == "work" and \
        not hasattr(_Target.work, "__wrapped__")
    (t0, t1, extra), = rec.calls["bench.work"]
    assert t1 >= t0 and extra == 4
    assert rec.within("bench.work", t0, t1 + 1) == [(t0, t1, 4)]


def test_missing_attribute_gives_null_metrics_and_a_named_warning(capsys):
    rec = probes.Recorder().install([
        _Probe("bench.resolve", __name__, "_Target.renamed_away")])
    assert rec.missing == {"bench.resolve": f"{__name__}._Target.renamed_away"}
    assert "bench.resolve" in capsys.readouterr().err
    read = harness.load_reader("resolve_p50_s")
    r = harness.Records(t0=0.0, t1=1.0, probes=rec)
    assert read(r) is None


def test_every_probe_file_names_a_method_of_the_program():
    rec = probes.Recorder()
    with warnings.catch_warnings():
        rec.install(probes.load_probe_files(ROOT / "bench" / "probes"))
    try:
        assert rec.missing == {}
        assert len(rec.calls) == 7
    finally:
        rec.uninstall()


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------

TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            vocab_size=1000, max_len=256)


def _tiny(name):
    spec = _load(ROOT / "bench" / "configs" / f"{name}.json")
    spec.update(TINY)
    return spec


CELL = "qwen3-4b.chat-short"


@pytest.mark.parametrize("seed", [2**32 + 5, 77])
def test_fp8_control_fails_where_the_program_passes(seed):
    """The cell's configuration at its published widths, cut to one
    layer and a 4096-word vocabulary so a test run holds it. Teacher
    forced on the same tokens, the bf16 program's greedy tokens pass the
    cell's own ``logit_gap_limit``; the tokens the fp8 reference ranks
    first, put in the program's place, do not."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import Model
    b = _load(ROOT / "BENCHMARK.json")
    w = {x["name"]: x for x in b["workloads"]}[CELL]
    conf = {x["name"]: x for x in b["configs"]}[w["config"]]
    spec = {**_load(ROOT / conf["file"]), "num_hidden_layers": 1,
            "vocab_size": 4096}
    limit = _load(ROOT / "bench" / "cells" / f"{CELL}.json")[
        "logit_gap_limit"]
    sh = dense.Shape(spec)
    params = dense.make_params_jit(sh, jnp.asarray(dense.base_key(seed)))
    cfg = get_config(spec["registry"]).replace(
        n_layers=sh.n_layers, d_model=sh.d, n_heads=sh.h, n_kv_heads=sh.kv,
        head_dim=sh.dh, d_ff=sh.ff, vocab=sh.vocab, tie_embeddings=sh.tied,
        rope_theta=sh.theta, qk_norm=sh.qk_norm)
    toks = np.random.default_rng(0).integers(16, sh.vocab, (3, 64))
    prog = np.asarray(Model(cfg, dtype=jnp.bfloat16).forward(
        params, {"tokens": jnp.asarray(toks, jnp.int32)}))[..., :sh.vocab]
    rows = [(b, p) for b in range(3) for p in range(64)]
    ref = dense.Reference(sh).logits_at(seed, toks, rows)
    fp8 = dense.Reference(sh, fp8=True).logits_at(seed, toks, rows)
    for served, want in [(prog.reshape(-1, sh.vocab).argmax(-1), True),
                         (fp8.argmax(-1), False)]:
        gap = float(dense.served_gaps(ref, served).max())
        correct, checks = harness.judge(gap, len(rows), 0, limit)
        assert correct is want, checks


def _cell(name, mix):
    b = _load(ROOT / "BENCHMARK.json")
    cell = {"rate_per_s": 3.0, "drain_s": 30, "check_requests": 4,
            "logit_gap_limit": 0.02}
    return harness.CellSpec.of(f"tiny.{name}", _tiny(name), mix, cell,
                               end_to_end=b["end_to_end"])


SUPPORT = {"shared_prompts": {"count": 3, "role": "system",
                              "tokens": {"dist": "loguniform", "lo": 24,
                                         "hi": 64},
                              "zipf_a": 1.1, "rank_of_prompt": [1, 0, 2]},
           "user_tokens": {"dist": "uniform", "lo": 8, "hi": 16},
           "output_tokens": {"dist": "loguniform", "lo": 4, "hi": 10}}


def _run(spec, seed=2**31 + 3):
    jax = harness.setup_jax()
    return harness.run(spec, seed, 3.0, False, time.perf_counter(), jax)


def test_a_sound_run_is_correct():
    out = _run(_cell("qwen3-4b", SUPPORT))
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["logit_gap"]["value"] <= 0.02
    assert set(out["metrics"]) == {"ttft_p50_s", "ttft_p90_s",
                                   "ttlt_p90_s", "itl_mean_s", "setup_s"}


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.serving.scheduler import Scheduler
    orig = Scheduler._sample

    def altered(self, logits):
        tok = orig(self, logits)
        return (tok + 1) % logits.shape[-1]
    monkeypatch.setattr(Scheduler, "_sample", altered)
    out = _run(_cell("yi-6b", {"shared_prompts": None,
                               "user_tokens": {"dist": "loguniform",
                                               "lo": 16, "hi": 48},
                               "output_tokens": {"dist": "uniform",
                                                 "lo": 4, "hi": 8}}))
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > 0.02


def test_a_failed_request_is_not_correct(monkeypatch):
    """A sound program whose one window request errors where it is
    admitted: the served tokens still pass, the run does not."""
    from repro.serving.scheduler import Scheduler
    orig = Scheduler.submit
    window = []

    def flaky(self, req):
        if req.tenant != "warm":
            window.append(req)
            if len(window) == 2:
                raise RuntimeError("dropped where it is admitted")
        return orig(self, req)
    monkeypatch.setattr(Scheduler, "submit", flaky)
    out = _run(_cell("qwen3-4b", {"shared_prompts": None,
                                  "user_tokens": {"dist": "loguniform",
                                                  "lo": 8, "hi": 24},
                                  "output_tokens": {"dist": "uniform",
                                                    "lo": 4, "hi": 8}}))
    assert out["failed"] == 1
    assert out["checks"]["failed_requests"] == {"value": 1, "limit": 0}
    assert out["checks"]["logit_gap"]["value"] <= 0.02
    assert out["correct"] is False


def test_no_chip_means_no_result(capsys, monkeypatch):
    import run as bench_run  # bench/run.py
    assert bench_run.main(["--workload", CELL,
                           "--seed", "1", "--seconds", "1"]) == 3
    out = capsys.readouterr()
    assert out.out == "" and "no accelerator" in out.err


sys.path.insert(0, str(ROOT / "bench"))
