"""Uploads pull only the prompt's positions of a slot to the host: the
device-side cut (``BatchedEngine.slot_cache``) leaves every range's
container byte-identical to one built from the whole slot, and compiles
once per pull bucket, whatever the slot or the prompt's length."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import state_io
from repro.core.chunkfmt import pack_container
from repro.core.keys import model_meta
from repro.models import Model
from repro.obs.trace import Tracer
from repro.serving import engine as engine_mod
from repro.serving.engine import BatchedEngine

MAX_LEN = 160
SLOT = 1

# case: (config, overrides, prompt length, positions each sequence leaf
# of the cut keeps, or None where it keeps its whole capacity)
CASES = {
    "dense_gqa": ("qwen3-4b", {}, 30, 64),
    # a ring of 96 holds 120 positions wrapped: it ships whole
    "window_wrapped": ("qwen3-4b", {"window": 96}, 120, None),
    # the same ring unwrapped, larger than the pull: cut at 64
    "window_unwrapped": ("qwen3-4b", {"window": 96}, 30, 64),
    "mla": ("deepseek-v3-671b", {}, 40, 64),
    # global attention cut, conv/ssd whole; 8 meta tokens put 60 tokens
    # at 68 positions, so the pull is 128 of 168
    "hybrid_ssm": ("hymba-1.5b", {"window": 0}, 60, 128),
    "under_64": ("qwen3-4b", {}, 63, 64),
    "over_64": ("qwen3-4b", {}, 65, 128),
    "at_max_len": ("qwen3-4b", {}, MAX_LEN, MAX_LEN),
}

_MODELS = {}


def _model(name, overrides):
    key = (name, tuple(sorted(overrides.items())))
    if key not in _MODELS:
        cfg = get_config(name).reduced().replace(**overrides)
        model = Model(cfg)
        _MODELS[key] = (cfg, model, model.init(jax.random.PRNGKey(0)))
    return _MODELS[key]


def _filled_engine(cfg, model, params, n, batch_size=2):
    """An engine whose slot ``SLOT`` holds an ``n``-token prompt and
    whose other slots hold other prompts."""
    eng = BatchedEngine(model, params, max_len=MAX_LEN,
                        batch_size=batch_size)
    rng = np.random.default_rng(n)
    if cfg.family == "moe":
        # the batched engine cannot vmap the MoE FFN (ragged_dot); the
        # cut sees only the cache, so fill it with values of its dtype
        eng.cache = jax.tree.map(
            lambda a: rng.standard_normal(a.shape).astype(a.dtype),
            eng.cache)
        return eng, rng.standard_normal((1, cfg.vocab)).astype(np.float32)
    rows = [rng.integers(3, cfg.vocab, n).astype(np.int32)
            for _ in range(batch_size)]
    logits = eng.prefill_slots(list(range(batch_size)), rows)
    return eng, logits[SLOT:SLOT + 1]


def _containers(cache1, bounds, meta, logits, quantize):
    lists = state_io.extract_state_ranges(cache1, bounds, meta,
                                          logits=logits, quantize=quantize)
    return {n: pack_container(c) for n, c in lists.items()}


@pytest.mark.parametrize("case", list(CASES))
def test_cut_slot_ships_the_same_blobs_as_the_whole_slot(case):
    name, overrides, n, kept = CASES[case]
    cfg, model, params = _model(name, overrides)
    eng, logits = _filled_engine(cfg, model, params, n)
    n_positions = model.cache_len(n)
    cut = eng.slot_cache(SLOT, n_positions)
    whole = jax.tree.map(lambda a: a[:, SLOT:SLOT + 1], eng.cache)
    for path, a in jax.tree_util.tree_flatten_with_path(cut)[0]:
        w = whole
        for p in path:
            w = w[getattr(p, "key", getattr(p, "idx", None))]
        if path[-1].key in state_io.SEQ_LEAVES and kept is not None \
                and w.shape[2] > kept:
            assert a.shape[2] == kept, (case, path)
        else:
            assert a.shape == w.shape, (case, path)
    # every range a prompt of n tokens can have, as the gateway keys it
    bounds = sorted({model.cache_len(k) for k in (1, n // 3, n // 2, n)
                     if k})
    meta = model_meta(cfg, "float32")
    for quantize in (False, True):
        assert _containers(cut, bounds, meta, logits, quantize) == \
            _containers(whole, bounds, meta, logits, quantize), \
            (case, quantize)


def test_slot_cut_compiles_once_per_bucket_and_pulls_only_it():
    cfg, model, params = _model("qwen3-4b", {})
    eng, _ = _filled_engine(cfg, model, params, 40, batch_size=3)
    # the cut is jitted once for the module: count the shapes added here
    before = engine_mod._cut_slot._cache_size()
    for slot in range(3):
        for n in (1, 17, 40, 63, 64):
            assert eng.pull_positions(n) == 64
            eng.slot_cache(slot, n)
    assert engine_mod._cut_slot._cache_size() == before + 1
    eng.slot_cache(0, 65)
    assert engine_mod._cut_slot._cache_size() == before + 2

    tr = Tracer(proc="t")
    with tr.start("gw.upload"):
        state_io.extract_state_ranges(eng.slot_cache(SLOT, 40), [40],
                                      model_meta(cfg, "float32"))
    (to_host,) = [d for d in tr.spans() if d["name"] == "upload.to_host"]
    leaves = jax.tree.leaves(eng.cache)
    assert all(a.shape[2] == MAX_LEN for a in leaves)
    assert to_host["attrs"]["nbytes"] == sum(
        a.nbytes // a.shape[1] // MAX_LEN * 64 for a in leaves)
