"""Inference engines: single-request and batched (continuous batching).

``InferenceEngine`` is the substrate the paper's EdgeClient drives:

  * ``start(inputs)``                     — fresh prefill (Case 1, miss)
  * ``resume(suffix, cache, n_prefix)``   — continue from a downloaded
                                            prompt-cache prefix (Cases 2-4)
  * ``adopt(cache, n_tokens, logits)``    — full hit (Case 5): no compute
  * ``generate(state, n, sampler)``       — autoregressive decode loop

``BatchedEngine`` generalizes it to a fixed pool of B cache *slots* with
independent per-slot positions — the substrate of the continuous-batching
``Scheduler`` (serving/scheduler.py). Per-slot positions are expressed by
vmapping the single-row model calls over the cache's batch axis, so every
slot decodes at its own offset in one fused device step.

All model calls are jitted once per (shape bucket). Prefill inputs are
padded to power-of-two buckets to bound recompilation.

Importing this module puts the program's spans on the profiler's clock:
it installs ``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation``
for a span carrying ``step_num``) as :mod:`repro.obs.trace`'s
host-annotation factory, so every context-managed span also appears
under its name in a ``jax.profiler`` trace's host plane.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.state_io import SEQ_LEAVES
from repro.obs import clock as oclock
from repro.obs import trace as otrace
from repro.obs.trace import phase
from repro.serving.sampler import greedy


def _annotation(name: str, attrs: dict):
    step = attrs.get("step_num")
    if step is not None:
        return jax.profiler.StepTraceAnnotation(name, step_num=step)
    return jax.profiler.TraceAnnotation(name)


otrace.set_annotation(_annotation)


def _bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


@dataclass
class EngineState:
    cache: Any
    pos: int                       # next token position (excl. meta offset)
    last_logits: np.ndarray        # [B, V]
    tokens: list = field(default_factory=list)   # generated tokens
    timings: Dict[str, float] = field(default_factory=dict)


class InferenceEngine:
    def __init__(self, model, params, max_len: int, cache_dtype=None):
        self.model = model
        self.params = params
        self.max_len = max_len            # in prompt-token space
        self.cache_dtype = cache_dtype or model.dtype
        self._prefill_fn = {}
        self._decode = jax.jit(
            lambda p, c, t, pos: model.decode_step(p, c, t, pos))
        # layer-streamed resume: jitted pieces, keyed per layer group
        self._stream_embed = jax.jit(model.prefill_stream_embed) \
            if model.supports_layer_stream else None
        self._stream_head = jax.jit(model.prefill_stream_head)
        self._stream_group = {}

    @property
    def supports_layer_stream(self) -> bool:
        return self.model.supports_layer_stream

    # ------------------------------------------------------------------
    def new_cache(self):
        return self.model.init_cache(
            1, self.model.cache_len(self.max_len), self.cache_dtype)

    def _prefill_jit(self, resume: bool):
        if resume not in self._prefill_fn:
            self._prefill_fn[resume] = jax.jit(
                partial(self.model.prefill, resume=resume))
        return self._prefill_fn[resume]

    def _pad_inputs(self, inputs: Dict[str, np.ndarray]):
        """Pad token dim to a bucket; returns (padded, true_len)."""
        key = "embeds" if "embeds" in inputs else "tokens"
        n = inputs[key].shape[1]
        b = min(_bucket(n), self.max_len)
        if b == n:
            return inputs, n
        pad = b - n
        out = dict(inputs)
        if key == "tokens":
            out["tokens"] = np.pad(inputs["tokens"], ((0, 0), (0, pad)),
                                   mode="edge")
        else:
            out["embeds"] = np.pad(inputs["embeds"],
                                   ((0, 0), (0, pad), (0, 0)))
            out["positions"] = np.pad(inputs["positions"],
                                      ((0, 0), (0, 0), (0, pad)), mode="edge")
        return out, n

    # ------------------------------------------------------------------
    def start(self, inputs) -> EngineState:
        """Fresh prefill of the full prompt (cache miss)."""
        return self._run_prefill(inputs, self.new_cache(), 0, resume=False)

    def resume(self, inputs, cache, n_prefix: int) -> EngineState:
        """Continue prefill from a restored prefix of ``n_prefix`` tokens."""
        return self._run_prefill(inputs, cache, n_prefix, resume=True)

    def adopt(self, cache, n_tokens: int, logits: np.ndarray) -> EngineState:
        """Full hit: adopt a downloaded state with no model execution."""
        return EngineState(cache=cache, pos=n_tokens, last_logits=logits)

    def resume_streamed(self, inputs, n_prefix: int, groups) -> EngineState:
        """Layer-streamed resume: run the suffix prefill one layer group
        at a time, as the downloaded cache chunks land.

        ``groups`` yields ``(si, lo, hi, cache_group)`` in compute order
        (segment-major, ascending layer ranges, jointly covering every
        layer) — typically a generator blocking on a
        :class:`~repro.core.state_io.ChunkedRestorer`'s completed
        groups, so layers [lo:hi) of the suffix execute while the
        chunks for layers >= hi are still on the wire. Numerically the
        monolithic resume: scanning layers [0:L) equals scanning [0:k)
        then [k:L). The returned state's ``timings['prefill_wall']`` is
        the *compute* time only (transfer stalls excluded), which is
        what the client charges as p_decode on the wall breakdown."""
        if not self.supports_layer_stream:
            raise NotImplementedError(
                f"layer-streamed resume unsupported for family "
                f"{self.model.cfg.family!r}")
        t0 = oclock.monotonic()
        padded, true_n = self._pad_inputs(inputs)
        if self.model.cfg.window:      # ring caches cannot take padding
            padded, true_n = inputs, inputs[
                "embeds" if "embeds" in inputs else "tokens"].shape[1]
        compute = 0.0
        tc = oclock.monotonic()
        x, positions, eff_start = self._stream_embed(
            self.params, padded, n_prefix)
        jax.block_until_ready(x)
        compute += oclock.monotonic() - tc
        n_segs = len(self.model.segments)
        new_segs = [[] for _ in range(n_segs)]
        next_layer = [0] * n_segs
        for si, lo, hi, cache_group in groups:
            if not (0 <= si < n_segs) or lo != next_layer[si]:
                raise ValueError(
                    f"stream group (seg {si}, layers {lo}:{hi}) out of "
                    f"order (expected layer {next_layer[si] if 0 <= si < n_segs else '?'})")
            tc = oclock.monotonic()
            x, nc = self._group_fn(si, lo, hi)(
                self.params, x, positions, cache_group, eff_start)
            jax.block_until_ready(x)
            compute += oclock.monotonic() - tc
            new_segs[si].append(nc)
            next_layer[si] = hi
        for si, seg in enumerate(self.model.segments):
            if next_layer[si] != seg.n_layers:
                raise ValueError(
                    f"stream ended with segment {si} at layer "
                    f"{next_layer[si]}/{seg.n_layers}")
        tc = oclock.monotonic()
        logits = self._stream_head(self.params, x, true_n - 1)
        logits = np.asarray(jax.block_until_ready(logits))
        compute += oclock.monotonic() - tc
        cache = {"segments": [
            jax.tree.map(lambda *parts: jnp.concatenate(parts, axis=0),
                         *parts_list) if len(parts_list) > 1
            else parts_list[0]
            for parts_list in new_segs]}
        st = EngineState(cache=cache, pos=n_prefix + true_n,
                         last_logits=logits)
        st.timings["prefill_wall"] = compute
        st.timings["prefill_tokens"] = true_n
        st.timings["stream_wall"] = oclock.monotonic() - t0
        return st

    def _group_fn(self, si: int, lo: int, hi: int):
        key = (si, lo, hi)
        if key not in self._stream_group:
            self._stream_group[key] = jax.jit(partial(
                self.model.prefill_stream_group, si=si, lo=lo, hi=hi))
        return self._stream_group[key]

    def _run_prefill(self, inputs, cache, start_pos, *, resume):
        t0 = oclock.monotonic()
        padded, true_n = self._pad_inputs(inputs)
        # padding beyond the true prompt writes junk KV at positions
        # >= start_pos + true_n; they are never attended (causal) as long as
        # the next prefill/decode starts at start_pos + true_n. Ring caches
        # are the exception — for windowed models we avoid padding.
        if self.model.cfg.window:
            padded, true_n = inputs, inputs[
                "embeds" if "embeds" in inputs else "tokens"].shape[1]
        fn = self._prefill_jit(resume)
        logits, cache = fn(self.params, padded, cache, start_pos, true_n - 1)
        logits = np.asarray(jax.block_until_ready(logits))
        wall = oclock.monotonic() - t0
        st = EngineState(cache=cache, pos=start_pos + true_n,
                         last_logits=logits)
        st.timings["prefill_wall"] = wall
        st.timings["prefill_tokens"] = true_n
        return st

    # ------------------------------------------------------------------
    def decode_one(self, st: EngineState, token: np.ndarray) -> np.ndarray:
        """Feed ``token`` [B,1], return logits [B,V]; advances state."""
        logits, st.cache = self._decode(self.params, st.cache,
                                        jnp.asarray(token, jnp.int32),
                                        st.pos)
        st.pos += 1
        st.last_logits = np.asarray(jax.block_until_ready(logits))
        return st.last_logits

    def generate(self, st: EngineState, max_tokens: int,
                 sampler: Callable = greedy, eos_id: Optional[int] = None,
                 rng=None) -> np.ndarray:
        t0 = oclock.monotonic()
        out = []
        logits = st.last_logits
        for _ in range(max_tokens):
            tok = sampler(logits, rng)           # [B]
            out.append(tok)
            if eos_id is not None and np.all(tok == eos_id):
                break
            logits = self.decode_one(st, tok[:, None])
        st.timings["decode_wall"] = oclock.monotonic() - t0
        st.timings["decode_tokens"] = len(out)
        st.tokens.extend(int(t[0]) for t in out)
        return np.stack(out, axis=1)


# ---------------------------------------------------------------------------
# batched engine (continuous batching substrate)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=2)
def _cut_slot(cache, slot, n_pull: int):
    """Row ``slot`` of every ``[L, B, ...]`` leaf; sequence leaves cut on
    axis 2 to their first ``n_pull`` positions. The slot is traced and
    the cut static: one compile per pull bucket, not per slot."""
    def cut(path, a):
        if getattr(path[-1], "key", None) in SEQ_LEAVES:
            a = a[:, :, :min(n_pull, a.shape[2])]
        return lax.dynamic_slice_in_dim(a, slot, 1, axis=1)
    return jax.tree_util.tree_map_with_path(cut, cache)


class BatchedEngine:
    """Fixed pool of ``batch_size`` cache slots with per-slot positions.

    The model's ``decode_step``/``prefill`` take one *scalar* position for
    the whole batch; continuous batching needs every slot at its own
    offset. We get that by vmapping the single-row call over the cache's
    batch axis (axis 1 of every ``[L, B, ...]`` leaf): each slot is
    computed with B=1 semantics — numerically the path of a sequential
    ``InferenceEngine`` run — but all slots execute as one fused device
    step, which is where the aggregate-throughput win comes from
    (benchmarks/serving_throughput.py).

    Slot lifecycle (driven by the Scheduler):
      ``prefill_slots``  — bucket-padded batched prefill of fresh prompts
      ``resume_slot``    — single-row prefill from a downloaded prefix
      ``adopt_slot``     — install a fully-restored state (full hit)
      ``decode_batch``   — advance every active slot one token
      ``free_slot``      — recycle on EOS/max-tokens (stale KV needs no
                           scrub: position masks hide entries beyond the
                           next request's written range)
    """

    def __init__(self, model, params, max_len: int, batch_size: int,
                 cache_dtype=None):
        if model.cfg.window and model.cfg.window < max_len:
            # ring caches cannot take bucket padding (the rebuild would
            # rotate junk in); prefill_slots falls back to per-row exact
            # prefill for windowed models.
            self._pad_prefill = False
        else:
            self._pad_prefill = True
        self.model = model
        self.params = params
        self.max_len = max_len
        self.batch_size = batch_size
        self.cache_dtype = cache_dtype or model.dtype
        self.cache = model.init_cache(
            batch_size, model.cache_len(max_len), self.cache_dtype)
        self.pos = np.zeros(batch_size, np.int32)     # next token position
        self._decode_b = jax.jit(jax.vmap(
            self._decode_one, in_axes=(None, 1, 0, 0), out_axes=(0, 1)))
        # fresh batched prefill: the per-row zero cache is materialized
        # inside the jitted body (fused away by XLA) so the engine never
        # holds a second pool-sized cache allocation
        self._prefill_fresh_b = jax.jit(jax.vmap(
            self._prefill_one_fresh, in_axes=(None, 0, 0, 0),
            out_axes=(0, 1)))
        self._prefill_1: Dict[bool, Any] = {}

    # -- vmapped single-row bodies -------------------------------------
    def _decode_one(self, p, c_row, tok, pos):
        """c_row: cache with batch axis removed ([L, ...] leaves)."""
        c = jax.tree.map(lambda a: jnp.expand_dims(a, 1), c_row)
        logits, nc = self.model.decode_step(p, c, tok[None, None], pos)
        return logits[0], jax.tree.map(lambda a: jnp.squeeze(a, 1), nc)

    def _prefill_one_fresh(self, p, toks, start, last):
        c = self.model.init_cache(1, self.model.cache_len(self.max_len),
                                  self.cache_dtype)
        logits, nc = self.model.prefill(p, {"tokens": toks[None]}, c,
                                        start, last, resume=False)
        return logits[0], jax.tree.map(lambda a: jnp.squeeze(a, 1), nc)

    def _prefill_single(self, resume: bool):
        if resume not in self._prefill_1:
            self._prefill_1[resume] = jax.jit(
                partial(self.model.prefill, resume=resume))
        return self._prefill_1[resume]

    # -- slot plumbing ---------------------------------------------------
    def _scatter_rows(self, rows, slots: Sequence[int], n_rows: int):
        """Write rows[:, :n_rows] of a batched cache into ``slots``."""
        idx = jnp.asarray(np.asarray(slots[:n_rows], np.int32))
        self.cache = jax.tree.map(
            lambda big, new: big.at[:, idx].set(new[:, :n_rows]),
            self.cache, rows)

    def pull_positions(self, n_positions: int) -> int:
        """Sequence positions :meth:`slot_cache` keeps for an upload of
        ``n_positions`` cache positions: their power-of-two bucket, at
        least 64, capped at the slot's capacity. Below 64 positions
        (9.4 MB of qwen3-4b's bf16 K/V) the pull to the host costs its
        fixed overhead, not its bytes; the floor also gives every short
        prompt one pull shape, so a server warmed with one short request
        compiles no new cut for later ones."""
        return min(_bucket(n_positions, minimum=64),
                   self.model.cache_len(self.max_len))

    def slot_cache(self, slot: int, n_positions: int):
        """The B=1 cache of ``slot`` for state_io extraction, cut on the
        device to what an upload of ``n_positions`` cache positions
        needs: sequence leaves (``SEQ_LEAVES``) to
        :meth:`pull_positions` positions, or their capacity if smaller
        (a ring that wrapped ships whole), every other leaf whole."""
        return _cut_slot(self.cache, jnp.int32(slot),
                         self.pull_positions(n_positions))

    def free_slot(self, slot: int) -> None:
        self.pos[slot] = 0

    def adopt_slot(self, slot: int, cache1, n_tokens: int) -> None:
        """Install a restored B=1 cache (full prompt-cache hit)."""
        idx = jnp.asarray([slot])
        self.cache = jax.tree.map(
            lambda big, row: big.at[:, idx].set(
                row.astype(big.dtype) if row.dtype != big.dtype else row),
            self.cache, cache1)
        self.pos[slot] = n_tokens

    # -- prefill ---------------------------------------------------------
    def prefill_slots(self, slots: Sequence[int],
                      token_rows: Sequence[np.ndarray]) -> np.ndarray:
        """Bucket-padded batched prefill of fresh prompts into ``slots``.

        Rows are edge-padded to one shared power-of-two bucket and the
        batch dim is padded to ``batch_size`` (so compile count is bounded
        by the number of buckets, not admission patterns). Returns the
        true last-token logits [len(slots), V].
        """
        k = len(slots)
        assert k and k <= self.batch_size
        lens = [int(t.shape[-1]) for t in token_rows]
        if not self._pad_prefill:
            return np.concatenate(
                [self.prefill_slot(s, t) for s, t in zip(slots, token_rows)])
        bucket = min(_bucket(max(lens)), self.max_len)
        toks = np.zeros((self.batch_size, bucket), np.int32)
        for i, t in enumerate(token_rows):
            row = np.asarray(t, np.int32).reshape(-1)
            toks[i, :len(row)] = row
            toks[i, len(row):] = row[-1]          # edge pad
        starts = np.zeros(self.batch_size, np.int32)
        lasts = np.zeros(self.batch_size, np.int32)
        lasts[:k] = np.asarray(lens, np.int32) - 1
        logits, rows = self._prefill_fresh_b(
            self.params, jnp.asarray(toks),
            jnp.asarray(starts), jnp.asarray(lasts))
        with phase("prefill.wait", bucket=bucket):
            logits = np.asarray(jax.block_until_ready(logits))
        with phase("prefill.scatter"):
            self._scatter_rows(rows, list(slots), k)
        for s, n in zip(slots, lens):
            self.pos[s] = n
        return logits[:k]

    def prefill_slot(self, slot: int, tokens: np.ndarray,
                     cache1=None, start_pos: int = 0) -> np.ndarray:
        """Exact-length single-row prefill into ``slot``.

        ``cache1``/``start_pos``: resume from a downloaded prefix state
        (B=1 cache holding ``start_pos`` tokens). Returns logits [1, V].
        """
        resume = start_pos > 0
        if cache1 is None:
            cache1 = self.model.init_cache(
                1, self.model.cache_len(self.max_len), self.cache_dtype)
        toks = jnp.asarray(np.asarray(tokens, np.int32).reshape(1, -1))
        n = toks.shape[1]
        fn = self._prefill_single(resume)
        logits, nc = fn(self.params, {"tokens": toks}, cache1,
                        start_pos, n - 1)
        with phase("prefill.wait", bucket=n):
            logits = np.asarray(jax.block_until_ready(logits))
        idx = jnp.asarray([slot])
        with phase("prefill.scatter"):
            self.cache = jax.tree.map(
                lambda big, row: big.at[:, idx].set(
                    row.astype(big.dtype) if row.dtype != big.dtype
                    else row),
                self.cache, nc)
        self.pos[slot] = start_pos + n
        return logits

    # -- decode ------------------------------------------------------------
    def decode_batch(self, tokens: np.ndarray,
                     active: Optional[np.ndarray] = None) -> np.ndarray:
        """One decode step for the whole pool. ``tokens``: [B] int32 (pad
        rows arbitrary); ``active``: [B] bool mask — inactive rows step at
        position 0 and their (junk) writes are overwritten/masked on the
        slot's next use. Returns logits [B, V]; advances active positions.
        """
        if active is None:
            active = np.ones(self.batch_size, bool)
        pos = np.where(active, self.pos, 0).astype(np.int32)
        logits, self.cache = self._decode_b(
            self.params, self.cache,
            jnp.asarray(np.asarray(tokens, np.int32)), jnp.asarray(pos))
        self.pos = np.where(active, self.pos + 1, self.pos).astype(np.int32)
        with phase("decode.wait"):
            return np.asarray(jax.block_until_ready(logits))
