"""Continuous-batching scheduler: equivalence, admission, recycling."""
import numpy as np
import pytest

from repro.serving.engine import BatchedEngine, InferenceEngine
from repro.serving.sampler import greedy
from repro.serving.scheduler import Request, Scheduler


@pytest.fixture(scope="module")
def batched(tiny_setup):
    cfg, model, params = tiny_setup
    return BatchedEngine(model, params, max_len=64, batch_size=4)


@pytest.fixture(scope="module")
def single(tiny_setup):
    cfg, model, params = tiny_setup
    return InferenceEngine(model, params, max_len=64)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab, (n,)).astype(np.int32) for n in lens]


def _sequential(single, prompts, max_new):
    out = []
    for p in prompts:
        st = single.start({"tokens": p[None]})
        out.append(list(map(int, np.asarray(
            single.generate(st, max_new, greedy))[0])))
    return out


def test_batched_prefill_logits_match_sequential(tiny_setup, batched,
                                                 single):
    """Bucket-padded batched prefill rows == single-engine prefill."""
    cfg, model, params = tiny_setup
    prompts = _prompts(cfg, (21, 9, 30, 17), seed=1)
    batched.pos[:] = 0
    logits = batched.prefill_slots([0, 1, 2, 3], prompts)
    for i, p in enumerate(prompts):
        ref = single.start({"tokens": p[None]}).last_logits
        np.testing.assert_allclose(logits[i], ref[0], atol=2e-5, rtol=1e-4)
    assert list(batched.pos) == [21, 9, 30, 17]


def test_b4_token_identical_to_four_sequential_runs(tiny_setup, batched,
                                                    single):
    """The acceptance bar: B=4 greedy == 4 sequential engine runs."""
    cfg, model, params = tiny_setup
    prompts = _prompts(cfg, (21, 9, 30, 17), seed=2)
    ref = _sequential(single, prompts, max_new=8)
    batched.pos[:] = 0
    sched = Scheduler(batched)
    stats = sched.run([Request(tokens=p, max_new_tokens=8)
                       for p in prompts])
    assert [stats[i].output_tokens for i in range(4)] == ref


def test_more_requests_than_slots_recycles(tiny_setup, batched, single):
    """8 requests over 4 slots: slots recycle, outputs stay exact, and
    the decode-iteration count shows batching (not serial drain)."""
    cfg, model, params = tiny_setup
    prompts = _prompts(cfg, (12, 26, 9, 18, 22, 15, 11, 24), seed=3)
    ref = _sequential(single, prompts, max_new=6)
    batched.pos[:] = 0
    sched = Scheduler(batched)
    stats = sched.run([Request(tokens=p, max_new_tokens=6)
                       for p in prompts])
    assert [stats[i].output_tokens for i in range(8)] == ref
    seq_steps = sum(len(o) - 1 for o in ref)
    assert sched.n_steps < seq_steps      # genuinely batched
    assert all(s.finish_reason == "length" for s in stats.values())


def test_admission_is_fifo(tiny_setup, batched):
    cfg, model, params = tiny_setup
    prompts = _prompts(cfg, (10,) * 7, seed=4)
    batched.pos[:] = 0
    sched = Scheduler(batched)
    stats = sched.run([Request(tokens=p, max_new_tokens=4)
                       for p in prompts])
    admits = [stats[i].admit_t for i in range(7)]
    assert admits == sorted(admits)       # FIFO admission order
    # the first batch_size requests were admitted before any later one
    assert max(admits[:4]) <= min(admits[4:])
    # later arrivals waited for a recycled slot
    assert all(stats[i].queue_wait >= 0 for i in range(7))


def test_eos_recycles_slot_early(tiny_setup, batched, single):
    cfg, model, params = tiny_setup
    prompts = _prompts(cfg, (14, 14), seed=5)
    ref = _sequential(single, prompts, max_new=8)
    # greedy decoding on random weights repeats tokens, so the EOS must
    # be a token whose FIRST occurrence is the stop index; take the
    # latest such index before the length limit
    stop = max(i for i in range(1, 7) if ref[0][i] not in ref[0][:i])
    eos = ref[0][stop]
    batched.pos[:] = 0
    sched = Scheduler(batched)
    stats = sched.run([
        Request(tokens=prompts[0], max_new_tokens=8, eos_id=eos),
        Request(tokens=prompts[1], max_new_tokens=8),
    ])
    assert stats[0].output_tokens == ref[0][:stop + 1]   # stopped at EOS
    assert stats[0].finish_reason == "eos"
    assert stats[1].output_tokens == ref[1]         # unaffected neighbour
    assert stats[1].finish_reason == "length"


def test_decode_logits_match_sequential(tiny_setup, batched, single):
    """Per-slot vmapped decode == scalar-pos single decode, step by step."""
    cfg, model, params = tiny_setup
    prompts = _prompts(cfg, (13, 27), seed=6)
    refs = []
    for p in prompts:
        st = single.start({"tokens": p[None]})
        logits = [st.last_logits[0]]
        tok = np.argmax(logits[-1])[None]
        for _ in range(3):
            logits.append(single.decode_one(st, tok[:, None])[0])
            tok = np.argmax(logits[-1])[None]
        refs.append(logits)
    batched.pos[:] = 0
    lg = batched.prefill_slots([0, 1], prompts)
    active = np.array([True, True, False, False])
    got = [[lg[0]], [lg[1]]]
    toks = np.zeros(4, np.int32)
    for _ in range(3):
        toks[:2] = [np.argmax(got[0][-1]), np.argmax(got[1][-1])]
        step = batched.decode_batch(toks, active)
        got[0].append(step[0])
        got[1].append(step[1])
    for i in range(2):
        for a, b in zip(got[i], refs[i]):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)


def test_report_percentiles(tiny_setup, batched):
    cfg, model, params = tiny_setup
    prompts = _prompts(cfg, (8, 8, 8), seed=7)
    batched.pos[:] = 0
    sched = Scheduler(batched)
    sched.run([Request(tokens=p, max_new_tokens=3) for p in prompts])
    rep = sched.report()
    assert rep.n_requests == 3
    assert rep.total_output_tokens == 9
    assert rep.throughput_tok_s > 0
    assert 0 <= rep.ttft_p50 <= rep.ttft_p90 <= rep.ttft_p99
    assert rep.latency_p50 <= rep.latency_p99
