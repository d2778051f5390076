"""Dense GQA decoder (Qwen3, Llama/Yi): weights, plain reference, work.

A model family is one file ``bench/families/<family>.py``; a
configuration file names it under ``"family"``, and the harness loads
it by that name (``harness.load_family``) and calls nothing else of it.
Its interface, which this file is the first to fill:

* ``Shape(spec)``: the sizes the configuration file ``spec`` states;
  at least ``vocab`` and ``n_layers``. It is what a per-layer metric
  reads as ``Records.shape``.
* ``program_config(spec, base)``: the program's ``ModelConfig`` for
  ``spec``, from the registry's ``base``.
* ``make_params_jit(shape, key)``: the served weights, from the raw key
  ``common.base_key(seed)``, in the program's layout.
* ``Reference(shape, fp8=False)``: ``.logits_at(seed, tokens, pos,
  row_cap)`` and, after it, ``.first_layer_s``; ``fp8=True`` is the
  control.
* ``decode_work(shape, live)``: ``(flops, bytes)`` of one decode step.
* ``weight_params(shape)``: the weights' parameter count.

What every family shares is in ``bench/lib/common.py``. Everything here
is the benchmark's own and imports nothing of the program (but
``program_config`` fills the program's config object it is handed):

* ``make_params`` draws the served weights from the seed, on the
  device, in one jitted call, in bf16 and in the layout the program's
  ``Model`` takes. Every leaf comes from its own key
  (seed, layer, leaf), so the reference can draw any one layer again
  with the same numbers.
* ``Reference`` is a straightforward float32 forward pass at
  ``precision=highest`` that follows the published architecture
  (RMSNorm with the config's epsilon, optional per-head q/k RMSNorm,
  rotate-half RoPE, grouped-query causal attention, SwiGLU). It runs
  layer by layer over a block of sequences, drawing each layer's
  weights as it goes, so it fits beside nothing else on the chip.
  ``fp8=True`` rounds every matrix-product operand to float8_e4m3fn
  with a per-tensor scale: the control, one precision below bf16.
* ``decode_work`` counts the FLOPs and HBM bytes one decode step needs
  from shapes alone: all weights once, plus K/V at each active row's
  live length.
"""
from __future__ import annotations

import time
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.common import base_key, mm, rms

LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2",
                "w_up", "w_gate", "w_down")
NORM_STD = 0.1               # norm gains are 1 + N(0, 0.1^2)
EMBED_STD = 0.02             # the configs' initializer_range


class Shape:
    """The sizes a dense GQA config file states (HF key names)."""

    def __init__(self, spec: dict):
        self.n_layers = int(spec["num_hidden_layers"])
        self.d = int(spec["hidden_size"])
        self.h = int(spec["num_attention_heads"])
        self.kv = int(spec["num_key_value_heads"])
        self.dh = int(spec.get("head_dim") or self.d // self.h)
        self.ff = int(spec["intermediate_size"])
        self.vocab = int(spec["vocab_size"])
        self.tied = bool(spec["tie_word_embeddings"])
        self.theta = float(spec["rope_theta"])
        self.eps = float(spec["rms_norm_eps"])
        self.qk_norm = bool(spec["qk_norm"])
        self.vocab_padded = -(-self.vocab // 256) * 256

    def leaf_shape(self, name: str) -> Tuple[Tuple[int, ...], float]:
        d, h, kv, dh, ff = self.d, self.h, self.kv, self.dh, self.ff
        return {
            "ln1": ((d,), NORM_STD), "ln2": ((d,), NORM_STD),
            "q_norm": ((dh,), NORM_STD), "k_norm": ((dh,), NORM_STD),
            "wq": ((d, h, dh), d ** -0.5), "wk": ((d, kv, dh), d ** -0.5),
            "wv": ((d, kv, dh), d ** -0.5),
            "wo": ((h, dh, d), (h * dh) ** -0.5),
            "w_up": ((d, ff), d ** -0.5), "w_gate": ((d, ff), d ** -0.5),
            "w_down": ((ff, d), ff ** -0.5),
        }[name]

    def layer_leaves(self) -> List[str]:
        return [n for n in LAYER_LEAVES
                if self.qk_norm or n not in ("q_norm", "k_norm")]


def program_config(spec: dict, base):
    """The program's ``ModelConfig``: the registry's ``base`` with the
    sizes ``spec`` states."""
    sh = Shape(spec)
    return base.replace(
        n_layers=sh.n_layers, d_model=sh.d, n_heads=sh.h,
        n_kv_heads=sh.kv, head_dim=sh.dh, d_ff=sh.ff, vocab=sh.vocab,
        tie_embeddings=sh.tied, rope_theta=sh.theta, qk_norm=sh.qk_norm)


def layer_leaf(key, layer, name: str, shape: Shape):
    """One layer's leaf, bf16, from (seed, layer, leaf)."""
    dims, std = shape.leaf_shape(name)
    k = jax.random.fold_in(jax.random.fold_in(key, 1000 + layer),
                           LAYER_LEAVES.index(name))
    return (jax.random.normal(k, dims, jnp.float32) * std).astype(
        jnp.bfloat16)


def embed_rows(key, shape: Shape):
    """[vocab, d] bf16: the input embedding (and the tied head)."""
    k = jax.random.fold_in(key, 1)
    return (jax.random.normal(k, (shape.vocab, shape.d), jnp.float32)
            * EMBED_STD).astype(jnp.bfloat16)


def head_cols(key, shape: Shape):
    """[d, vocab] bf16: the untied output head."""
    k = jax.random.fold_in(key, 2)
    return (jax.random.normal(k, (shape.d, shape.vocab), jnp.float32)
            * EMBED_STD).astype(jnp.bfloat16)


def final_norm(key, shape: Shape):
    k = jax.random.fold_in(key, 3)
    return (jax.random.normal(k, (shape.d,), jnp.float32)
            * NORM_STD).astype(jnp.bfloat16)


def make_params(shape: Shape, key) -> Dict:
    """The served weights in the program's layout (norms stored as the
    offset from 1; vocabulary padded to a multiple of 256 with zero
    rows, which the program masks)."""
    pad = shape.vocab_padded - shape.vocab
    layers = jnp.arange(shape.n_layers)

    def stack(name):
        return jax.vmap(lambda l: layer_leaf(key, l, name, shape))(layers)

    attn = {n: stack(n) for n in ("wq", "wk", "wv", "wo")}
    if shape.qk_norm:
        attn["q_norm"], attn["k_norm"] = stack("q_norm"), stack("k_norm")
    seg = {"ln1": {"scale": stack("ln1")}, "ln2": {"scale": stack("ln2")},
           "attn": attn,
           "mlp": {n: stack(n) for n in ("w_up", "w_gate", "w_down")}}
    p = {"embed": jnp.pad(embed_rows(key, shape), ((0, pad), (0, 0))),
         "final_norm": {"scale": final_norm(key, shape)},
         "segments": [seg]}
    if not shape.tied:
        p["head"] = jnp.pad(head_cols(key, shape), ((0, 0), (0, pad)))
    return p


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _rope(x, theta):
    """x [S, heads, dh]; rotate-half RoPE at positions 0..S-1."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Reference:
    """Float32 forward pass over a block of equal-length sequences."""

    def __init__(self, shape: Shape, fp8: bool = False):
        self.s = shape
        self.fp8 = fp8
        self._layer = jax.jit(self._layer_fn)
        self._embed = jax.jit(self._embed_fn)
        self._head = jax.jit(self._head_fn)

    def _w(self, key, layer, name):
        return layer_leaf(key, layer, name, self.s).astype(jnp.float32)

    def _embed_fn(self, key, tokens):
        return jnp.take(embed_rows(key, self.s).astype(jnp.float32),
                        tokens, axis=0)

    def _attn_one(self, w, h):
        """h [S, d] of one sequence -> attention output [S, d]."""
        s, f8 = self.s, self.fp8
        q = mm("sd,dhk->shk", h, w["wq"], f8)
        k = mm("sd,dhk->shk", h, w["wk"], f8)
        v = mm("sd,dhk->shk", h, w["wv"], f8)
        if s.qk_norm:
            q = rms(q, w["q_norm"], s.eps)
            k = rms(k, w["k_norm"], s.eps)
        q, k = _rope(q, s.theta), _rope(k, s.theta)
        rep = s.h // s.kv
        k = jnp.repeat(k, rep, axis=1)          # head i uses kv head i//rep
        v = jnp.repeat(v, rep, axis=1)
        sc = mm("qhk,shk->hqs", q, k, f8) / np.sqrt(s.dh)
        n = h.shape[0]
        causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = mm("hqs,shk->qhk", pr, v, f8)
        return mm("qhk,hkd->qd", o, w["wo"], f8)

    def _layer_fn(self, key, layer, x):
        """x [B, S, d] -> x after decoder layer ``layer``."""
        s, f8 = self.s, self.fp8
        w = {n: self._w(key, layer, n) for n in s.layer_leaves()}
        h = rms(x, w["ln1"], s.eps)
        x = x + jax.lax.map(lambda hb: self._attn_one(w, hb), h)
        h = rms(x, w["ln2"], s.eps)
        g = mm("bsd,df->bsf", h, w["w_gate"], f8)
        u = mm("bsd,df->bsf", h, w["w_up"], f8)
        return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, w["w_down"], f8)

    def _head_fn(self, key, x, b, p):
        """Logits [N, vocab] at the positions (b[i], p[i]) of the final
        hidden states x [B, S, d]."""
        s = self.s
        g = final_norm(key, s).astype(jnp.float32)
        h = rms(x[b, p], g, s.eps)
        if s.tied:
            return mm("nd,vd->nv", h,
                      embed_rows(key, s).astype(jnp.float32), self.fp8)
        return mm("nd,dv->nv", h, head_cols(key, s).astype(jnp.float32),
                  self.fp8)

    def logits_at(self, seed: int, tokens: np.ndarray,
                  rows: Sequence[Tuple[int, int]],
                  row_cap: int = 0) -> np.ndarray:
        """tokens [B, S] (padded at the end; causal, so padding never
        reaches an earlier position); rows: (sequence, position) pairs,
        padded to ``row_cap`` so one program serves every run.
        Returns the logits [len(rows), vocab] at those positions."""
        t0 = time.perf_counter()
        key = jnp.asarray(base_key(seed))
        x = self._embed(key, jnp.asarray(tokens, jnp.int32))
        for layer in range(self.s.n_layers):
            x = self._layer(key, jnp.int32(layer), x)
            if layer == 0:
                x.block_until_ready()
                self.first_layer_s = time.perf_counter() - t0
        n = len(rows)
        b = np.zeros(max(row_cap, n), np.int32)
        p = np.zeros(max(row_cap, n), np.int32)
        b[:n] = [r[0] for r in rows]
        p[:n] = [r[1] for r in rows]
        out = np.asarray(self._head(key, x, jnp.asarray(b), jnp.asarray(p)))
        del x
        return out[:n]


# ---------------------------------------------------------------------------
# required work of a decode step
# ---------------------------------------------------------------------------

def weight_params(shape: Shape) -> int:
    s = shape
    per_layer = (s.d * s.h * s.dh + 2 * s.d * s.kv * s.dh
                 + s.h * s.dh * s.d + 3 * s.d * s.ff + 2 * s.d
                 + (2 * s.dh if s.qk_norm else 0))
    return s.n_layers * per_layer + s.vocab * s.d + s.d


def decode_work(shape: Shape, live: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) one decode step needs for active rows whose
    caches hold ``live`` tokens each, counting the new one. bf16
    weights and K/V; float32 logits out."""
    s = shape
    rows = len(live)
    mat = (s.n_layers * (s.d * s.h * s.dh + 2 * s.d * s.kv * s.dh
                         + s.h * s.dh * s.d + 3 * s.d * s.ff)
           + s.d * s.vocab)
    attn = s.n_layers * 2 * 2 * s.h * s.dh * sum(live)
    flops = 2.0 * rows * mat + attn
    kv_row = s.n_layers * 2 * s.kv * s.dh * 2            # bytes per token
    nbytes = (2.0 * weight_params(shape)
              + kv_row * sum(live)                         # K/V read
              + (0 if s.tied else rows * s.d * 2)          # embedding rows
              + rows * s.vocab * 4)                        # logits out
    return flops, nbytes


make_params_jit = jax.jit(make_params, static_argnums=0)
