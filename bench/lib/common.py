"""What every model family in ``bench/families/`` shares.

Family-independent and the benchmark's own, importing nothing of the
program: the raw key every family draws its weights from, the check of
made weights against the program's parameter layout, the gap of a
served token below the reference's best, and the float32 arithmetic of
the plain references (matrix products at ``precision=highest``, with
the fp8 control's operand rounding, and RMSNorm).
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def base_key(seed: int) -> np.ndarray:
    """A raw threefry key for any seed up to 63 bits."""
    seed = int(seed)
    return np.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                      np.uint32)


def check_layout(params, expected) -> List[str]:
    """Differences between the weights made here and the program's own
    parameter shapes (``jax.eval_shape`` of its init)."""
    got = jax.tree_util.tree_flatten_with_path(params)[0]
    want = jax.tree_util.tree_flatten_with_path(expected)[0]
    g = {jax.tree_util.keystr(p): (tuple(a.shape), str(a.dtype))
         for p, a in got}
    w = {jax.tree_util.keystr(p): (tuple(a.shape), str(a.dtype))
         for p, a in want}
    return [f"{k}: made {g.get(k)} program {w.get(k)}"
            for k in sorted(set(g) | set(w)) if g.get(k) != w.get(k)]


def served_gaps(logits: np.ndarray, served: Sequence[int]) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at that position."""
    idx = np.arange(len(served))
    return logits.max(axis=-1) - logits[idx, np.asarray(served)]


def q8(x):
    """Round to float8_e4m3fn with a per-tensor scale; back to f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(spec: str, a, b, fp8: bool):
    """A float32 einsum at ``precision=highest``; with ``fp8`` both
    operands rounded by ``q8`` first (the control)."""
    if fp8:
        a, b = q8(a), q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms(x, gain_offset, eps):
    """RMSNorm over the last axis, gains stored as the offset from 1."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + gain_offset)
