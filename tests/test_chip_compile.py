"""The served path's device programs compile for a TPU v5e at full width.

``qwen3-4b`` in bf16 at its published widths, against a described
(not attached) ``v5e:2x2`` topology, one chip: the batched decode step,
the vmapped fresh prefill and a resume prefill of ``BatchedEngine``.
Nothing runs; the chip's compiler refuses what would not fit or lower,
and ``memory_analysis()`` says what one program needs on the device.
Shapes come from ``jax.eval_shape``, so no weights are made.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import Model
from repro.serving.engine import BatchedEngine

HBM_BYTES = 16 * 2**30          # TPU v5e: 16 GiB of HBM per chip
BATCH, MAX_LEN = 4, 1024         # the gateway's pool in chip_smoke.py


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def served(one_chip):
    """(engine, sds) — a BatchedEngine over parameter *shapes* and a
    helper that places a shape on the described chip."""
    model = Model(get_config("qwen3-4b"), dtype=jnp.bfloat16)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    eng = BatchedEngine(model, params, MAX_LEN, BATCH)

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    return eng, sds


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < HBM_BYTES, m
    return total


def test_batched_decode_step_compiles(served):
    eng, sds = served
    i32 = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    c = eng._decode_b.lower(sds(eng.params), sds(eng.cache), sds(i32),
                            sds(i32)).compile()
    # the weights alone are ~8 GB in bf16: the program holds them
    assert _fits(c) > 8e9


def test_vmapped_fresh_prefill_compiles(served):
    eng, sds = served
    toks = jax.ShapeDtypeStruct((BATCH, 512), jnp.int32)
    i32 = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    c = eng._prefill_fresh_b.lower(sds(eng.params), sds(toks), sds(i32),
                                   sds(i32)).compile()
    _fits(c)


def test_resume_prefill_compiles(served):
    eng, sds = served
    cache1 = jax.eval_shape(lambda: eng.model.init_cache(
        1, eng.model.cache_len(MAX_LEN), eng.cache_dtype))
    suffix = {"tokens": jax.ShapeDtypeStruct((1, 37), jnp.int32)}
    scalar = jax.ShapeDtypeStruct((), np.int32)
    c = eng._prefill_single(True).lower(
        sds(eng.params), sds(suffix), sds(cache1), sds(scalar),
        sds(scalar)).compile()
    _fits(c)
