"""Mixture-of-Experts layer: top-k routing, sort + ``lax.ragged_dot`` compute.

Two execution paths share the same parameters:

* ``moe_local``  — single-device reference (tests, smoke, serving engine).
* ``moe_ep``     — expert-parallel ``shard_map``: experts sharded over the
  ``model`` mesh axis, tokens replicated over it (they are already sharded
  over the data axes); each shard computes its local experts' contribution
  with a capacity-bounded sorted gather + ``ragged_dot`` and the shard
  outputs are ``psum``-combined. Overflow beyond capacity is dropped
  (standard capacity-factor semantics); ``ragged_dot`` zero-fills rows past
  ``sum(group_sizes)`` so non-local rows cost nothing.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import activation, dense_init, opt_barrier

_shard_map = partial(jax.shard_map, check_vma=False)

# tokens processed per inner MoE chunk on each shard (bounds transients)
_TOKEN_CHUNK = 8192


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def stored_experts(cfg) -> int:
    """Expert-dim storage size: padded to a multiple of 16 so the expert
    dimension always shards evenly over the 'model' mesh axis (the padded
    experts receive no routed tokens and contribute zero FLOPs via
    ragged_dot's group sizes)."""
    e = cfg.moe.n_experts
    return -(-e // 16) * 16 if e >= 16 else e


def init_moe(key, cfg, dtype):
    mo = cfg.moe
    d, f = cfg.d_model, mo.expert_ff
    es = stored_experts(cfg)
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (d, mo.n_experts), jnp.float32),
        "w_up": dense_init(ks[1], (es, d, f), dtype),
        "w_down": dense_init(ks[2], (es, f, d), dtype),
    }
    if cfg.gated_mlp:
        p["w_gate"] = dense_init(ks[3], (es, d, f), dtype)
    if mo.n_shared:
        fs = (mo.shared_ff or mo.expert_ff) * mo.n_shared
        p["ws_up"] = dense_init(ks[4], (d, fs), dtype)
        p["ws_down"] = dense_init(ks[5], (fs, d), dtype)
        if cfg.gated_mlp:
            p["ws_gate"] = dense_init(ks[6], (d, fs), dtype)
    return p


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def route(p, cfg, xf):
    """xf: [T, D] -> (weights [T,k], ids [T,k], aux_loss scalar)."""
    mo = cfg.moe
    logits = xf.astype(jnp.float32) @ p["router"]          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, mo.top_k)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-9)    # renormalize
    # switch-style load-balance loss
    frac = jnp.mean(jax.nn.one_hot(ids, mo.n_experts, dtype=jnp.float32),
                    axis=(0, 1))                            # importance
    load = jnp.mean(probs, axis=0)
    aux = mo.n_experts * jnp.sum(frac * load) * mo.aux_coef
    return w, ids, aux


def _expert_ffn(xs, p, cfg, gs, lo=None, hi=None):
    """ragged expert FFN over sorted rows xs [C, D] with group sizes gs."""
    sl = slice(lo, hi)
    h = jax.lax.ragged_dot(xs, p["w_up"][sl], gs)
    if cfg.gated_mlp:
        g = jax.lax.ragged_dot(xs, p["w_gate"][sl], gs)
        h = activation(g, cfg.act) * h
    else:
        h = activation(h, cfg.act)
    return jax.lax.ragged_dot(h, p["w_down"][sl], gs)


def _shared_ffn(x, p, cfg):
    h = x @ p["ws_up"]
    if cfg.gated_mlp:
        h = activation(x @ p["ws_gate"], cfg.act) * h
    else:
        h = activation(h, cfg.act)
    return h @ p["ws_down"]


# ---------------------------------------------------------------------------
# local (single-shard) path
# ---------------------------------------------------------------------------

def moe_local(p, cfg, x):
    """x: [B, S, D] -> (out, aux)."""
    B, S, D = x.shape
    mo = cfg.moe
    xf = x.reshape(-1, D)
    T = xf.shape[0]
    w, ids, aux = route(p, cfg, xf)
    eid = ids.reshape(-1)
    tid = jnp.repeat(jnp.arange(T), mo.top_k)
    order = jnp.argsort(eid)
    xs = xf[tid[order]]
    gs = jnp.bincount(eid, length=p["w_up"].shape[0]).astype(jnp.int32)
    y = _expert_ffn(xs, p, cfg, gs)
    wf = w.reshape(-1)[order].astype(y.dtype)
    out = jnp.zeros_like(xf).at[tid[order]].add(y * wf[:, None])
    if mo.n_shared:
        out = out + _shared_ffn(xf, p, cfg)
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# expert-parallel shard_map path
# ---------------------------------------------------------------------------

def moe_ep(p, cfg, x, mesh, *, ep_axis: str = "model",
           dp_axes: Optional[Sequence[str]] = None):
    """Expert-parallel MoE. x sharded over dp_axes on batch; experts sharded
    over ep_axis. Returns (out, aux) with out sharded like x."""
    mo = cfg.moe
    if dp_axes is None:
        dp_axes = tuple(a for a in mesh.axis_names if a != ep_axis)
    # keep only data axes whose running product divides the batch (small
    # decode batches replicate over the rest)
    kept, prod = [], 1
    for a in dp_axes:
        if x.shape[0] % (prod * mesh.shape[a]) == 0:
            kept.append(a)
            prod *= mesh.shape[a]
    dp_axes = tuple(kept)
    n_ep = mesh.shape[ep_axis]
    e_stored = p["w_up"].shape[0]
    e_pad = -(-e_stored // n_ep) * n_ep
    e_loc = e_pad // n_ep

    def pad_e(a):
        return jnp.pad(a, ((0, e_pad - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))

    w_up, w_down = pad_e(p["w_up"]), pad_e(p["w_down"])
    w_gate = pad_e(p["w_gate"]) if cfg.gated_mlp else None

    xspec = P(tuple(dp_axes) if dp_axes else None, None, None)
    espec = P(ep_axis, None, None)

    @partial(
        _shard_map, mesh=mesh,
        in_specs=(xspec, P(None, None), espec, espec,
                  espec if w_gate is not None else P(),
                  P(None, ep_axis) if mo.n_shared and cfg.gated_mlp else P(),
                  P(None, ep_axis) if mo.n_shared else P(),
                  P(ep_axis, None) if mo.n_shared else P()),
        out_specs=(xspec, P()),
    )
    def f(xl, router, w_up, w_down, w_gate, ws_gate, ws_up, ws_down):
        w_up, w_down, w_gate = opt_barrier(
            (w_up, w_down, w_gate))
        b, S, D = xl.shape
        xf = xl.reshape(-1, D)
        t = xf.shape[0]
        my = jax.lax.axis_index(ep_axis)
        lo = my * e_loc
        glp = {"w_up": w_up, "w_down": w_down}
        if cfg.gated_mlp:
            glp["w_gate"] = w_gate

        def chunk_fn(xc):
            """Route + expert-FFN one token chunk. Chunking bounds the
            sort/gather/ragged-VJP transients to O(chunk) instead of
            O(tokens-per-shard) — without it the ragged_dot backward
            materializes [t, D, E_loc] buffers (28+ GiB observed)."""
            tc = xc.shape[0]
            lp = dict(p, router=router)
            w, ids, aux = route(lp, cfg, xc)
            local = (ids >= lo) & (ids < lo + e_loc)
            eid = jnp.where(local, ids - lo, e_loc).reshape(-1)
            tid = jnp.repeat(jnp.arange(tc), mo.top_k)
            order = jnp.argsort(eid)
            cap = int(tc * mo.top_k / n_ep * mo.capacity_factor)
            cap = min(max(cap, 1), tc * mo.top_k)
            sel = order[:cap]
            eid_sel = eid[sel]
            gs = jnp.bincount(eid_sel, length=e_loc).astype(jnp.int32)
            xs = xc[tid[sel]]
            y = _expert_ffn(xs, glp, cfg, gs)
            wf = jnp.where(eid_sel < e_loc,
                           w.reshape(-1)[sel], 0.0).astype(y.dtype)
            out = jnp.zeros_like(xc).at[tid[sel]].add(y * wf[:, None])
            if mo.n_shared:
                sp = {"ws_up": ws_up, "ws_down": ws_down}
                if cfg.gated_mlp:
                    sp["ws_gate"] = ws_gate
                out = out + _shared_ffn(xc, sp, cfg)
            return out, aux

        tc = _TOKEN_CHUNK
        if t > tc:
            tpad = (-t) % tc
            xp = jnp.pad(xf, ((0, tpad), (0, 0))) if tpad else xf
            xcs = xp.reshape((t + tpad) // tc, tc, D)

            def body(_, xc):
                return None, jax.checkpoint(chunk_fn)(xc)
            _, (out, auxs) = jax.lax.scan(body, None, xcs)
            out = out.reshape(t + tpad, D)[:t]
            aux = jnp.mean(auxs)
        else:
            out, aux = chunk_fn(xf)
        out = jax.lax.psum(out, ep_axis)
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)  # identical over ep_axis
        return out.reshape(b, S, D), aux

    z = jnp.zeros((), x.dtype)
    return f(x, p["router"], w_up, w_down,
             w_gate if w_gate is not None else z,
             p.get("ws_gate", z), p.get("ws_up", z), p.get("ws_down", z))


# ---------------------------------------------------------------------------
# resident-expert path (decode): weights stay put, tiny token batch
# replicates. §Perf iteration: the weight-gather path moves ~1.4 GB of
# expert weights per layer to serve ~128 decode tokens; keeping experts
# resident moves only the [T, D] activations (a few MB) instead.
# ---------------------------------------------------------------------------

def moe_ep_resident(p, cfg, x, mesh):
    mo = cfg.moe
    names = mesh.axis_names
    ep_axes = tuple(a for a in ("model", "data") if a in names)
    f_axis = "pod" if "pod" in names else None
    n_ep = 1
    for a in ep_axes:
        n_ep *= mesh.shape[a]
    e_stored = p["w_up"].shape[0]
    e_loc = e_stored // n_ep
    espec = P(ep_axes, None, f_axis)
    dspec = P(ep_axes, f_axis, None)

    @partial(
        _shard_map, mesh=mesh,
        in_specs=(P(None, None, None), P(None, None), espec, dspec,
                  espec if cfg.gated_mlp else P()),
        out_specs=(P(None, None, None), P()),
    )
    def f(xl, router, w_up, w_down, w_gate):
        # pin the per-layer weight slices: stops XLA converting/hoisting
        # the full [L,E,D,F] stack to f32 outside the layer scan
        w_up, w_down, w_gate = opt_barrier(
            (w_up, w_down, w_gate))
        b, S, D = xl.shape
        xf = xl.reshape(-1, D)
        t = xf.shape[0]
        lp = dict(p, router=router)
        w, ids, aux = route(lp, cfg, xf)
        idx = 0
        for a in ep_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        lo = idx * e_loc
        local = (ids >= lo) & (ids < lo + e_loc)
        eid = jnp.where(local, ids - lo, e_loc).reshape(-1)
        tid = jnp.repeat(jnp.arange(t), mo.top_k)
        order = jnp.argsort(eid)
        cap = int(max(t * mo.top_k / n_ep * mo.capacity_factor, 8))
        cap = min(cap, t * mo.top_k)
        sel = order[:cap]
        eid_sel = eid[sel]
        gs = jnp.bincount(eid_sel, length=e_loc).astype(jnp.int32)
        xs = xf[tid[sel]]
        glp = {"w_up": w_up, "w_down": w_down}
        if cfg.gated_mlp:
            glp["w_gate"] = w_gate
        y = _expert_ffn(xs, glp, cfg, gs)    # F possibly pod-sharded: the
        wf = jnp.where(eid_sel < e_loc,      # psum below sums F-partials
                       w.reshape(-1)[sel], 0.0).astype(y.dtype)
        out = jnp.zeros_like(xf).at[tid[sel]].add(y * wf[:, None])
        axes = ep_axes + ((f_axis,) if f_axis else ())
        out = jax.lax.psum(out, axes)
        return out.reshape(b, S, D), aux

    z = jnp.zeros((), x.dtype)
    out, aux = f(x, p["router"], p["w_up"], p["w_down"],
                 p.get("w_gate", z))
    if mo.n_shared:   # shared expert: plain GSPMD tensor-parallel FFN
        sp = {k: v for k, v in p.items() if k.startswith("ws_")}
        B, S, D = x.shape
        out = out + _shared_ffn(x.reshape(-1, D), sp, cfg).reshape(B, S, D)
    return out, aux


def moe_forward(p, cfg, x, mesh=None, ep_axis: str = "model"):
    if mesh is None or ep_axis not in getattr(mesh, "axis_names", ()):
        return moe_local(p, cfg, x)
    tokens = x.shape[0] * x.shape[1]
    e_stored = p["w_up"].shape[0]
    n_md = mesh.shape[ep_axis] * mesh.shape.get("data", 1)
    if tokens <= 4096 and e_stored % n_md == 0:
        return moe_ep_resident(p, cfg, x, mesh)
    return moe_ep(p, cfg, x, mesh, ep_axis=ep_axis)
