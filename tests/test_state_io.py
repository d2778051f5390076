"""State blob (de)serialization — the transferable prompt cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch, prefill_inputs
from repro.configs import get_config
from repro.core import state_io
from repro.core.chunkfmt import pack_container
from repro.core.keys import model_meta
from repro.models import Model


@pytest.mark.parametrize("arch", ["gemma3-270m", "mamba2-780m",
                                  "hymba-1.5b", "deepseek-v3-671b",
                                  "whisper-base"])
def test_roundtrip_and_resume_equivalence(arch):
    """Serialize a 10-token prefix, restore into a fresh engine cache,
    resume with the suffix -> identical last-token logits."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    meta = model_meta(cfg, "float32")
    batch = make_batch(cfg, B=1, S=16)

    ref_cache = model.init_cache(1, model.cache_len(20))
    ref_logits, _ = model.prefill(params, prefill_inputs(cfg, batch),
                                  ref_cache)

    # producer: prefill prefix, extract
    c = model.init_cache(1, model.cache_len(20))
    _, c = model.prefill(params, prefill_inputs(cfg, batch, slice(0, 10)), c)
    blob = state_io.extract_state(c, model.cache_len(10), meta)

    # consumer: restore into a fresh template, resume the suffix
    template = model.init_cache(1, model.cache_len(20))
    payload = state_io.parse_state(blob, meta)
    cache, n_eff, logits = state_io.restore_state(payload, template)
    assert n_eff == model.cache_len(10) and logits is None
    lr, _ = model.prefill(params, prefill_inputs(cfg, batch, slice(10, 16)),
                          cache, start_pos=10, resume=True)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(ref_logits),
                               atol=2e-5, rtol=1e-4)


def test_bf16_cache_ranges_roundtrip_bit_exact():
    """A bf16 cache (what a chip serves) goes through the v3 chunked
    range path and back unchanged, bit for bit."""
    cfg = get_config("qwen3-4b").reduced()
    model = Model(cfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    meta = model_meta(cfg, "bfloat16")
    batch = make_batch(cfg, B=1, S=16)
    c = model.init_cache(1, model.cache_len(20))
    lg, c = model.prefill(params, prefill_inputs(cfg, batch), c)
    chunks = state_io.extract_state_ranges(c, [10, 16], meta,
                                           logits=np.asarray(lg))
    for n in (10, 16):
        payload = state_io.parse_state(pack_container(chunks[n]), meta)
        got, n_eff, _ = state_io.restore_state(
            payload, model.init_cache(1, model.cache_len(20)))
        assert n_eff == n
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(c)):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(a)[:, :, :n].view(np.uint16),
                np.asarray(b)[:, :, :n].view(np.uint16))


def test_integrity_rejection():
    cfg = get_config("gemma3-270m").reduced()
    model = Model(cfg)
    c = model.init_cache(1, 8)
    blob = state_io.extract_state(c, 4, b"model-A")
    with pytest.raises(ValueError, match="different model"):
        state_io.parse_state(blob, b"model-B")


def test_logits_roundtrip_and_compression():
    cfg = get_config("gemma3-270m").reduced()
    model = Model(cfg)
    c = model.init_cache(1, 8)
    lg = np.random.default_rng(0).normal(size=(1, cfg.vocab)).astype(
        np.float32)
    raw = state_io.extract_state(c, 4, b"m", logits=lg, compress=False)
    zst = state_io.extract_state(c, 4, b"m", logits=lg, compress=True)
    assert len(zst) < len(raw)
    _, _, lg2 = state_io.restore_state(state_io.parse_state(zst, b"m"),
                                       model.init_cache(1, 8))
    np.testing.assert_allclose(lg2, lg.astype(np.float16).astype(np.float32))


def test_truncation_strips_beyond_prefix():
    cfg = get_config("gemma3-270m").reduced()
    model = Model(cfg)
    c = model.init_cache(1, 32)
    short = state_io.extract_state(c, 4, b"m")
    full = state_io.extract_state(c, 32, b"m")
    assert len(short) < len(full)
