#!/usr/bin/env python3
"""Bring-up smoke: serve qwen3-4b at full width through the gateway on one TPU.

    python3 chip_smoke.py

Drives the served path once, through the entry points a user calls:
HTTP client -> gateway -> Bloom catalog and fetch over a TCP fleet of
two peer daemons -> blob decode -> host-to-HBM restore -> prefill or
resume -> batched decode -> sampling. The model is ``qwen3-4b`` at its
published depth and widths, in bf16, with random weights from a seed.

Requests, over plain ``http.client``:
  * warm-up   unrelated prompt; compiles the prefill bucket and decode step
  * cold      shared system prompt + user turn A; a miss, uploaded to peers
  * full hit  the same request again; adopts the peer's state, no prefill
  * partial   the system prompt + user turn B; resumes from the peer's
              system-prompt state and prefills the suffix
  * SSE       the partial request again, streamed

Checks (any failure exits non-zero and prints no result line): status 200
with the requested number of token ids everywhere; SSE ends in
``data: [DONE]`` with no error event; both warm requests matched a cached
prefix served by a peer; the full hit's first token equals the cold
request's; the fetcher uploaded with zero errors; the gateway answered no
5xx. Then, in this process, the partial hit's resume logits are compared
with a cold prefill of the same prompt (``LOGITS_REL_TOL``).

Every time printed is a host wall time. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Where JAX finds no TPU the script exits non-zero before building anything.
"""
import http.client
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SEED = 0
ARCH = "qwen3-4b"
BATCH, MAX_LEN, MAX_TOKENS = 4, 1024, 8
TIMEOUT_S = 600.0                # first requests include compiles

# Relative L2 distance allowed between the resume's and the cold
# prefill's last-token logits. Both compute the same function with
# different shapes, so their bf16 roundings differ. Each layer rounds
# its outputs to bf16 (unit roundoff 2^-8); over 36 residual layers
# independent roundings add like a random walk, to about
# sqrt(36) * 2^-8 = 0.023 of the logits' norm, which bounds how far
# either path is from exact. 2^-5 sits above that and below what a
# resume one position off gives even on random weights, which barely
# depend on context (about 0.05 at 36 layers, bf16, on the CPU).
LOGITS_REL_TOL = 2.0 ** -5

SYSTEM = (
    "You are the support assistant for a company that sells edge "
    "computing kits to schools. Answer in short numbered steps, name the "
    "exact menu or command the customer needs, never invent part "
    "numbers, and when a question is about billing, refunds or shipping "
    "dates, explain that a human agent will follow up by email within "
    "two working days and ask for the order number first.")
OTHER_SYSTEM = (
    "You are a travel planner for long rail journeys across Europe. "
    "Suggest routes with at most two changes, list the departure and "
    "arrival stations, prefer day trains over night trains unless asked, "
    "mention seat reservations where they are mandatory, keep the total "
    "budget in euros, and close every answer with one question about the "
    "traveller's luggage or mobility needs before booking anything.")
USER_A = "My kit does not boot after the firmware update. What should I try?"
USER_B = ("The fan on the board is loud during video calls and the case "
          "gets hot. Is that normal or should I send it back?")


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


def chat(system: str, user: str, stream: bool = False) -> dict:
    return {"model": ARCH, "max_tokens": MAX_TOKENS, "stream": stream,
            "user": "smoke",
            "messages": [{"role": "system", "content": system},
                         {"role": "user", "content": user}]}


def post(port: int, body: dict):
    """Unary request; returns (status, json body, host wall seconds)."""
    t0 = time.perf_counter()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=TIMEOUT_S)
    try:
        conn.request("POST", "/v1/chat/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
    finally:
        conn.close()
    return resp.status, json.loads(data), time.perf_counter() - t0


def post_sse(port: int, body: dict):
    """Streamed request; returns (status, [event payloads], host wall
    seconds to the first token event, total host wall seconds)."""
    t0 = time.perf_counter()
    first = None
    events = []
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=TIMEOUT_S)
    try:
        conn.request("POST", "/v1/chat/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        buf = b""
        while True:
            chunk = resp.read1(65536)
            if not chunk:
                break
            buf += chunk
            while b"\n\n" in buf:
                ev, buf = buf.split(b"\n\n", 1)
                if first is None and b'"token_id"' in ev:
                    first = time.perf_counter() - t0
                events.append(ev.decode())
    finally:
        conn.close()
    return resp.status, events, first, time.perf_counter() - t0


def token_ids(out: dict):
    return out["choices"][0]["token_ids"]


def serve_and_check(gw, fabric):
    """The requests and their checks. Returns (partial-hit body,
    partial-hit token ids)."""
    peers = set(fabric.peer_ids())
    results = {}

    def unary(name, body):
        status, out, wall = post(gw.port, body)
        c = out.get("cache", {})
        print(f"{name}: status {status}, host wall round trip {wall:.3f} s, "
              f"matched {c.get('matched_tokens')} of "
              f"{out.get('usage', {}).get('prompt_tokens')} prompt tokens, "
              f"served_by {c.get('served_by') or '-'}, "
              f"tokens {out['choices'][0]['token_ids'] if status == 200 else out}")
        check(status == 200, f"{name}: status 200")
        check(len(token_ids(out)) == MAX_TOKENS,
              f"{name}: {MAX_TOKENS} token ids")
        results[name] = out
        return out

    unary("warm-up (compiles prefill bucket + decode step)",
          chat(OTHER_SYSTEM, USER_A))
    cold = unary("cold", chat(SYSTEM, USER_A))
    check(cold["cache"]["matched_tokens"] == 0, "cold: a cache miss")
    # the miss's ranges are uploaded off the serving path; wait for the
    # PUTs, then for one catalog sync period so the Bloom view sees them
    check(gw.engine.fetcher.flush_uploads(TIMEOUT_S), "uploads drained")
    time.sleep(gw.engine.fetcher.directory.cache_cfg.sync_interval_s + 0.1)

    full = unary("full hit", chat(SYSTEM, USER_A))
    n_full = full["usage"]["prompt_tokens"]
    check(full["cache"]["matched_tokens"] == n_full,
          "full hit: whole prompt matched")
    check(full["cache"]["served_by"] in peers, "full hit: served by a peer")
    check(token_ids(full)[0] == token_ids(cold)[0],
          "full hit: first token equals the cold request's")

    partial_body = chat(SYSTEM, USER_B)
    partial = unary("partial hit (compiles the resume)", partial_body)
    k = partial["cache"]["matched_tokens"]
    check(0 < k < partial["usage"]["prompt_tokens"],
          "partial hit: a proper prefix matched")
    check(partial["cache"]["served_by"] in peers,
          "partial hit: served by a peer")

    status, events, first_s, wall = post_sse(
        gw.port, chat(SYSTEM, USER_B, stream=True))
    print(f"SSE: status {status}, {len(events)} events, host wall to first "
          f"token {first_s if first_s is None else round(first_s, 3)} s, "
          f"total {wall:.3f} s, last {events[-1] if events else None}")
    check(status == 200, "SSE: status 200")
    check(events and events[-1] == "data: [DONE]", "SSE: ends in [DONE]")
    payloads = [json.loads(e[len("data: "):]) for e in events[:-1]]
    check(not any("error" in p for p in payloads), "SSE: no error event")
    sse_tokens = [p["choices"][0]["token_id"] for p in payloads
                  if "token_id" in p["choices"][0]]
    check(sse_tokens == token_ids(partial),
          "SSE: same tokens as the unary partial hit")

    for name, req in zip(list(results) + ["SSE"], gw.engine.sched.done):
        st = req.stats
        print(f"  {name}: host wall TTFT in the engine {st.ttft:.3f} s, "
              f"queue wait {st.queue_wait:.3f} s")
    fetcher = gw.engine.fetcher
    check(fetcher.flush_uploads(TIMEOUT_S), "uploads drained")
    print(f"fetcher: {fetcher.stats}")
    check(fetcher.stats["uploads"] > 0, "fetcher: uploads > 0")
    check(fetcher.stats["upload_errors"] == 0, "fetcher: zero upload errors")
    check(gw.server.stats["errors_5xx"] == 0, "gateway: no 5xx answers")
    return partial_body, token_ids(partial)


def compare_resume_with_cold(gw, body, served_tokens):
    """Partial-hit resume vs cold prefill of the same prompt, on
    last-token logits, with the gateway's own engine and fetcher: fetch
    and restore the peer's blob through state_io, resume at the match."""
    import numpy as np

    from repro.gateway import protocol

    segs = protocol.tokenize_request(
        gw.tokenizer, protocol.parse_chat(body, MAX_LEN - 1))
    toks = np.asarray(segs.token_ids, np.int32)
    fetcher, eng = gw.engine.fetcher, gw.engine.engine
    t0 = time.perf_counter()
    cache1, k, _, served = fetcher.resolve(segs)
    print(f"resolve + restore of {k} tokens from {served}: host wall "
          f"{time.perf_counter() - t0:.3f} s")
    check(0 < k < len(toks), "resolve: partial hit")
    # logits are stored padded to a multiple of 256 with the tail at
    # -1e30, which would swamp any norm: compare the real vocabulary
    v = eng.model.cfg.vocab
    resumed = eng.prefill_slot(0, toks[k:], cache1, k)[0, :v]
    cold = eng.prefill_slots([0], [toks])[0, :v]
    eng.free_slot(0)
    check(np.isfinite(resumed).all() and np.isfinite(cold).all(),
          "logits finite")
    rel = float(np.linalg.norm(resumed - cold) / np.linalg.norm(cold))
    print(f"resume vs cold logits: relative L2 {rel:.6g} "
          f"(tolerance {LOGITS_REL_TOL:.6g}), max |diff| "
          f"{float(np.max(np.abs(resumed - cold))):.6g}, max |cold| "
          f"{float(np.max(np.abs(cold))):.6g}, argmax resumed "
          f"{int(np.argmax(resumed))} cold {int(np.argmax(cold))}")
    check(rel <= LOGITS_REL_TOL, "resume logits match cold prefill")
    check(int(np.argmax(resumed)) == served_tokens[0],
          "served partial hit's first token is argmax of these logits")


def run(cfg):
    """Build the model, serve, check, stop. Raises SmokeFailure."""
    import jax
    import jax.numpy as jnp

    from repro.core import Fabric
    from repro.gateway import Gateway
    from repro.models import Model

    t0 = time.perf_counter()
    model = Model(cfg, dtype=jnp.bfloat16)
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(SEED)))
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    n_bytes = sum(int(a.nbytes) for a in jax.tree.leaves(params))
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab}, dtype {model.dtype.__name__}; {n_params} params, "
          f"{n_bytes} bytes; init host wall {time.perf_counter() - t0:.3f} s")

    t0 = time.perf_counter()
    fabric = Fabric.tcp(n_peers=2).start()
    try:
        print(f"fleet {fabric!r} up: host wall "
              f"{time.perf_counter() - t0:.3f} s")
        t0 = time.perf_counter()
        gw = Gateway(model, params, fabric=fabric, batch_size=BATCH,
                     max_len=MAX_LEN, model_name=ARCH,
                     request_timeout_s=TIMEOUT_S).start(TIMEOUT_S)
        print(f"gateway {gw.url} up: host wall "
              f"{time.perf_counter() - t0:.3f} s")
        try:
            body, served_tokens = serve_and_check(gw, fabric)
        finally:
            gw.stop()
        # the engine thread has exited: this thread owns the device now
        compare_resume_with_cold(gw, body, served_tokens)
    finally:
        fabric.stop()
    print("gateway and fleet stopped")


def main() -> int:
    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"device: platform {d.platform}, kind {d.device_kind}, count "
          f"{len(devs)}; compile cache {cache_dir}")
    if d.platform != "tpu":
        print(f"FAIL: needs a TPU, JAX found platform {d.platform!r}",
              file=sys.stderr)
        return 2
    from repro.configs import get_config

    t0 = time.perf_counter()
    try:
        run(get_config(ARCH))
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(f"total host wall {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
