"""The gateway's engine thread: the only place JAX runs.

The asyncio HTTP server parses, validates, and admits requests, then
hands :class:`GatewayJob` objects to a single :class:`GatewayEngine`
thread that owns the :class:`BatchedEngine` + continuous-batching
:class:`Scheduler`. Tokens flow back through per-request asyncio
queues via ``loop.call_soon_threadsafe`` — the event loop never blocks
on the device and the device never sees two threads.

Prompt-cache integration mirrors ``EdgeClient`` but stays *blocking*
(the scheduler's per-slot resume path consumes a restored cache, not a
chunk stream — ``FetchPolicy(transfer='streamed')`` is rejected at
construction):

* before submit, :class:`PrefixFetcher` resolves the longest cached
  prefix range from the fabric (directory plan or single-box catalog)
  and the request resumes from it (full hit -> slot adoption);
* on a complete miss, the scheduler's ``on_prefill`` hook fires while
  the slot still holds the state: ranges are extracted once (engine
  thread — it is JAX work) and shipped to the fabric by a background
  uploader thread, off the serving path.
"""
from __future__ import annotations

import itertools
import queue
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.config import CacheConfig
from repro.core import state_io
from repro.core.catalog import Catalog
from repro.core.cluster.directory import PeerDirectory
from repro.core.cluster.planner import FetchAttempt, FetchPlanner
from repro.core.deadline import attach as deadline_attach
from repro.core.deadline import current_deadline, deadline_scope
from repro.core.fetch_policy import FetchPolicy
from repro.core.keys import model_meta
from repro.core.metrics import ServingReport, merge_peer_stats
from repro.core.session_pool import FetchBroker
from repro.core.transport import TransportError
from repro.gateway.protocol import ParsedRequest
from repro.obs import REGISTRY, clock as oclock
from repro.obs.flight import FLIGHT
from repro.obs.ledger import LEDGER, LEDGER_KEY
from repro.obs.metrics import DEFAULT_BUCKETS
from repro.obs.trace import (NULL_SPAN, NULL_TRACER, Tracer, current_span,
                             phase, unwatch_gc, watch_gc)
from repro.serving.scheduler import Request, Scheduler


class GatewayClosed(Exception):
    """Submit after stop() / engine death."""


class GatewayJob:
    """One admitted request in flight between the event loop and the
    engine thread. Events pushed to ``q`` (thread-safe via
    ``call_soon_threadsafe``): ``("token", id)``, ``("done", reason,
    meta)``, ``("error", message)``."""

    _ids = itertools.count()

    def __init__(self, parsed: ParsedRequest, segments, loop, q):
        self.parsed = parsed
        self.segments = segments
        self.loop = loop
        self.q = q
        self.rid = f"cmpl-{next(self._ids)}"
        self.created = int(oclock.wall())
        self.matched = 0
        self.served_by = ""
        # decision-ledger handoff: the record the fetcher's plan opened
        # (committed at finish, when the realized prefill is known),
        # the broker leader's record when this resolve was deduped, and
        # the winning attempt's transfer seconds
        self.decision = None
        self.dedup_of = None
        self.fetch_s = 0.0
        # root request span (opened by the HTTP front door, ended there
        # after the response is written); the engine thread parents its
        # resolve/slot spans onto ``span.ctx`` — explicit handoff
        self.span = None
        # when the HTTP thread put the job in the engine's inbox
        self.enqueued_t = 0.0

    def push(self, event: tuple) -> None:
        try:
            self.loop.call_soon_threadsafe(self.q.put_nowait, event)
        except RuntimeError:
            pass                      # loop already closed (shutdown)


class PrefixFetcher:
    """Blocking prompt-cache resolve/upload against one fabric view.

    ``view`` is whatever ``fabric.directory()`` returned: a
    :class:`PeerDirectory` (multi-peer) or an ``InProcTransport``
    (single box, catalog kept locally). Resolution runs on the engine
    thread (the restored cache feeds straight into slot adoption);
    upload PUTs run on a dedicated uploader thread so the wire never
    blocks serving — the per-link transports serialize concurrent
    requests internally.
    """

    def __init__(self, model, cache_dtype, max_len: int, view,
                 cache_cfg: CacheConfig,
                 broker: Optional[FetchBroker] = None,
                 tracer: Optional[Tracer] = None):
        self.tracer = tracer or NULL_TRACER
        self.model = model
        self.cache_dtype = cache_dtype
        self.max_len = max_len
        self.cache_cfg = cache_cfg
        dtype_name = np.dtype(cache_dtype).name \
            if not hasattr(cache_dtype, "name") else cache_dtype.name
        self.meta = model_meta(model.cfg, dtype_name)
        self.directory = view if isinstance(view, PeerDirectory) else None
        self.transport = None if self.directory is not None else view
        self.catalog = Catalog(cache_cfg)
        self.clock = getattr(view, "clock", None)
        if self.directory is not None:
            self.planner = FetchPlanner(
                self.directory, model.cfg, None,
                dtype_bytes=np.dtype(cache_dtype).itemsize,
                chunk_layers=cache_cfg.chunk_layers)
            self.planner.owner = "gateway"
        else:
            self.planner = None
        # (record, dedup_of, fetch_s) of the most recent resolve — the
        # engine thread attaches it to the job and commits at finish
        # (resolution is single-threaded on the engine thread)
        self.last_decision = (None, None, 0.0)
        self.broker = broker or FetchBroker()
        self._uploaded: "OrderedDict[bytes, None]" = OrderedDict()
        self.stats = {"resolves": 0, "hits": 0, "full_hits": 0,
                      "false_positives": 0, "bytes_down": 0,
                      "bytes_up": 0, "uploads": 0, "upload_errors": 0}
        self._upq: "queue.Queue" = queue.Queue()
        self._uploader = threading.Thread(target=self._upload_loop,
                                          daemon=True)
        self._uploader.start()

    # ------------------------------------------------------------------
    def _template(self):
        return self.model.init_cache(
            1, self.model.cache_len(self.max_len), self.cache_dtype)

    def sync(self) -> None:
        now = self.clock.now() if self.clock is not None \
            else oclock.monotonic()
        if self.directory is not None:
            self.directory.maybe_sync(now)
            return
        try:
            self.catalog.maybe_sync(self.transport, now)
        except TransportError as e:
            # stale catalog degrades to misses
            FLIGHT.record("catalog.sync_failed", client="gateway",
                          error=repr(e))

    # ------------------------------------------------------------------
    def resolve(self, segments) -> Tuple[object, int, object, str]:
        """Longest usable cached prefix for this prompt. Returns
        ``(cache1, matched_tokens, logits, served_by)`` —
        ``(None, 0, None, "")`` on a miss."""
        self.stats["resolves"] += 1
        keys = segments.keys(self.meta, self.cache_cfg.max_ranges,
                             self.cache_cfg.range_stride)
        n = len(segments.token_ids)
        min_match = self.cache_cfg.min_match_tokens
        ddl = current_deadline()
        if self.directory is not None:
            plan = self.planner.plan(keys, n, min_match=min_match,
                                     deadline_s=ddl.remaining()
                                     if ddl is not None else None)
        else:
            plan = [FetchAttempt(None, k) for k in keys
                    if k.n_tokens >= min_match
                    and self.catalog.lookup(k.digest)]
        # the plan() call above opened a decision record; close it at
        # request finish (the engine thread knows the realized prefill),
        # so only stash + annotate here
        rec = self.planner.last_decision \
            if self.planner is not None else None
        self.last_decision = (rec, None, 0.0)
        for att in plan:
            if ddl is not None and att.est_fetch_s >= ddl.remaining():
                # remaining budget can't cover the transfer: fall to
                # the next attempt / local prefill instead of blowing
                # the deadline harder
                LEDGER.note_attempt(
                    rec, peer=att.peer_id or "server",
                    range_tokens=att.key.n_tokens, result="deadline",
                    est_fetch_s=att.est_fetch_s)
                FLIGHT.record("fetch.deadline_skip", client="gateway",
                              peer=att.peer_id or "server",
                              est_fetch_s=att.est_fetch_s,
                              remaining_s=ddl.remaining())
                continue
            resp, dt, nb, shared, template = self._get(att)
            hit = bool(resp.get("ok") and resp.get("blob"))
            LEDGER.note_attempt(
                rec, peer=att.peer_id or "server",
                range_tokens=att.key.n_tokens,
                result=("dead" if resp.get("dead")
                        else "hit" if hit else "miss"),
                est_fetch_s=att.est_fetch_s, actual_s=dt, shared=shared)
            if self.directory is not None and att.peer_id is not None \
                    and not shared:
                # every planned attempt was catalog-predicted present,
                # so a miss here is a stale-Bloom false positive
                self.directory.record_get(
                    att.peer_id, hit, att.est_fetch_s, dt,
                    len(resp.get("blob") or b"") if hit else 0,
                    predicted_present=True)
            if resp.get("dead"):
                continue             # next attempt; never a hang
            if not hit:
                self.stats["false_positives"] += 1
                continue
            blob = resp["blob"]
            payload = state_io.parse_state(blob, self.meta)
            if template is None:
                template = self._template()
            cache, n_eff, logits = state_io.restore_state(payload,
                                                          template)
            if not shared:
                self.stats["bytes_down"] += len(blob)
                if att.peer_id is not None:
                    self.directory.note_fetch(att.key.digest, blob,
                                              att.peer_id)
            if rec is not None:
                if shared:
                    # broker follower: the leader's record owns this
                    # fetch; link ours to it instead of double-counting
                    self.last_decision = (rec, resp.get(LEDGER_KEY), dt)
                else:
                    resp[LEDGER_KEY] = rec["id"]
                    self.last_decision = (rec, None, dt)
            self.stats["hits"] += 1
            if att.key.n_tokens == n:
                self.stats["full_hits"] += 1
            return (cache, att.key.n_tokens, logits,
                    att.peer_id or "server")
        return None, 0, None, ""

    def _get(self, att: FetchAttempt):
        cand, peer_id = att.key, att.peer_id
        # the broker leader runs issue() on a helper thread; hand the
        # caller's ambient span across explicitly so the directory's
        # per-attempt net spans (and the peer's folded remote spans)
        # land in this request's trace
        caller = current_span()
        ddl = current_deadline()
        if peer_id is not None:
            def issue():
                with self.tracer.attach(caller), deadline_attach(ddl):
                    return self.directory.request(peer_id, "get",
                                                  {"key": cand.digest})
            key = (peer_id, cand.digest)
        else:
            def issue():
                with self.tracer.attach(caller), deadline_attach(ddl):
                    return self.transport.request("get",
                                                  {"key": cand.digest})
            key = cand.digest
        return self.broker.fetch(key, issue, prep=self._template)

    # ------------------------------------------------------------------
    def upload(self, segments, cache1, logits) -> int:
        """Extract this prompt's range states (one serialization pass,
        on the caller/engine thread — it is device work) and queue the
        PUTs for the uploader thread. Ranges this gateway already
        shipped are skipped — N identical cold prompts cost one
        upload, not N. Returns the bytes queued. Under a span, timed as
        ``upload.slice`` (the device copy finishing), then
        ``state.serialize`` > ``upload.to_host`` / ``upload.encode``."""
        keys = [k for k in segments.keys(self.meta,
                                         self.cache_cfg.max_ranges,
                                         self.cache_cfg.range_stride)
                if k.digest not in self._uploaded]
        if not keys:
            return 0
        if current_span() is not None:
            with phase("upload.slice"):
                # traced only: the device copy of the slot, and the work
                # it waits on, ends here, so the pull to the host is
                # timed apart from it
                cache1 = jax.block_until_ready(cache1)
        n = len(segments.token_ids)
        per_key = {k.digest: self.model.cache_len(k.n_tokens)
                   for k in keys}
        chunk_lists = state_io.extract_state_ranges(
            cache1, sorted(set(per_key.values())), self.meta,
            logits=(logits if any(k.n_tokens == n for k in keys)
                    else None),
            compress=self.cache_cfg.compress,
            level=self.cache_cfg.compress_level,
            quantize=self.cache_cfg.quantize,
            codec=self.cache_cfg.compress_codec,
            chunk_layers=self.cache_cfg.chunk_layers)
        blobs = []
        for k in keys:
            blobs.append((k.digest, state_io.pack_container(
                chunk_lists[per_key[k.digest]])))
            self._uploaded[k.digest] = None
            while len(self._uploaded) > 4096:
                self._uploaded.popitem(last=False)
        self._upq.put(blobs)
        return sum(len(b) for _, b in blobs)

    def _upload_loop(self) -> None:
        while True:
            blobs = self._upq.get()
            try:
                if blobs is None:
                    return
                for digest, blob in blobs:
                    try:
                        if self.directory is not None:
                            self.stats["bytes_up"] += \
                                self.directory.upload(digest, blob)
                        else:
                            resp, _, _ = self.transport.request(
                                "put", {"key": digest, "blob": blob},
                                advance_clock=False)
                            if resp.get("stored", True):
                                self.catalog.register(digest)
                                self.stats["bytes_up"] += len(blob)
                        self.stats["uploads"] += 1
                    except Exception as e:
                        self.stats["upload_errors"] += 1
                        FLIGHT.record("upload.failed", client="gateway",
                                      error=repr(e))
            finally:
                self._upq.task_done()

    def flush_uploads(self, timeout_s: float = 10.0) -> bool:
        """Block until every queued PUT has drained (benchmarks that
        want bytes_up to be final). Returns False on timeout.

        Waits on the queue's ``all_tasks_done`` condition — the same
        one ``task_done()`` notifies — instead of a sleep/poll loop,
        so the caller wakes the moment the drain completes."""
        deadline = oclock.monotonic() + timeout_s
        with self._upq.all_tasks_done:
            while self._upq.unfinished_tasks:
                remaining = deadline - oclock.monotonic()
                if remaining <= 0:
                    return False
                self._upq.all_tasks_done.wait(remaining)
        return True

    def close(self) -> None:
        self._upq.put(None)

    def peer_stats(self):
        if self.directory is None:
            return {}
        return merge_peer_stats([self.directory.peer_stats()],
                                estimator=self.directory.estimator)


class GatewayEngine:
    """Single-threaded serving core behind the HTTP front door.

    ``start()`` spawns the engine thread, which constructs the
    :class:`BatchedEngine` (first JAX touch), the scheduler, and the
    fabric view, then drains the job inbox: admit -> resolve prefix ->
    submit -> step -> publish new tokens. ``stop()`` drains and joins.
    """

    def __init__(self, model, params, batch_size: int = 4,
                 max_len: int = 512, fabric=None,
                 cache_cfg: CacheConfig = CacheConfig(),
                 policy: Optional[FetchPolicy] = None,
                 cache_dtype=None, admission=None,
                 tracer: Optional[Tracer] = None,
                 ttft_buckets=None, queue_wait_buckets=None):
        if policy is None:
            policy = FetchPolicy(transfer="blocking")
        if policy.transfer != "blocking" or policy.overlap:
            # the scheduler's resume path consumes a fully restored
            # cache — there is no slot-level chunk-stream consumer, so
            # a streamed/overlapped policy cannot be honored. Reject at
            # construction, not on the first partial hit.
            raise ValueError(
                "GatewayEngine requires FetchPolicy(transfer='blocking',"
                " overlap=False): the batched scheduler restores cached"
                " prefixes whole before slot adoption")
        self.model = model
        self.params = params
        self.batch_size = batch_size
        self.max_len = max_len
        self.fabric = fabric
        self.cache_cfg = cache_cfg
        self.policy = policy
        self.cache_dtype = cache_dtype
        self.admission = admission
        # one tracer for the whole gateway process: HTTP front door,
        # engine thread, scheduler, and fetcher all mint spans here, so
        # GET /v1/traces/<rid> resolves one complete tree
        self.tracer = tracer or Tracer(proc="gateway", max_traces=128)
        # bucket layouts are registration-time config: the registry's
        # first registration of a family wins, so deployments that care
        # about sub-5ms TTFT resolution pass their own edges here
        self._queue_wait_buckets = (tuple(queue_wait_buckets)
                                    if queue_wait_buckets else None)
        self._m_ttft = REGISTRY.histogram(
            "gateway_ttft_seconds", "submit-to-first-token per request",
            buckets=(tuple(ttft_buckets) if ttft_buckets
                     else DEFAULT_BUCKETS))
        self._m_latency = REGISTRY.histogram(
            "gateway_request_seconds", "submit-to-finish per request")
        self._m_inbox = REGISTRY.histogram(
            "gateway_inbox_wait_seconds",
            "inbox hand-off to engine pickup per request",
            **({"buckets": self._queue_wait_buckets}
               if self._queue_wait_buckets else {}))
        self.inbox: "queue.Queue[GatewayJob]" = queue.Queue()
        self._live: Dict[int, List] = {}      # req_id -> [job, req, sent]
        self._stop = threading.Event()
        self.ready = threading.Event()
        self.startup_error: Optional[BaseException] = None
        self.fetcher: Optional[PrefixFetcher] = None
        self.sched: Optional[Scheduler] = None
        self._thread: Optional[threading.Thread] = None
        self._t0 = 0.0
        self._n_steps = 0

    # ------------------------------------------------------------------
    def start(self, timeout_s: float = 120.0) -> "GatewayEngine":
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="gateway-engine")
        self._thread.start()
        self.ready.wait(timeout_s)
        if self.startup_error is not None:
            raise self.startup_error
        if not self.ready.is_set():
            raise TimeoutError("gateway engine failed to start")
        return self

    def stop(self, timeout_s: float = 30.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout_s)
        if self.fetcher is not None:
            self.fetcher.flush_uploads(timeout_s)
            self.fetcher.close()

    @property
    def alive(self) -> bool:
        return (self._thread is not None and self._thread.is_alive()
                and not self._stop.is_set())

    def submit(self, job: GatewayJob) -> None:
        if not self.alive:
            raise GatewayClosed("gateway engine is not running")
        job.enqueued_t = oclock.monotonic()
        self.inbox.put(job)

    # ------------------------------------------------------------------
    def _run(self) -> None:
        try:
            from repro.serving.engine import BatchedEngine
            self.engine = BatchedEngine(self.model, self.params,
                                        self.max_len, self.batch_size,
                                        cache_dtype=self.cache_dtype)
            self.sched = Scheduler(
                self.engine, on_prefill=self._on_prefill,
                tracer=self.tracer,
                queue_wait_buckets=self._queue_wait_buckets)
            if self.fabric is not None:
                view = self.fabric.directory()
                self.fetcher = PrefixFetcher(
                    self.model, self.engine.cache_dtype, self.max_len,
                    view, self.cache_cfg, tracer=self.tracer)
        except BaseException as e:            # noqa: BLE001
            self.startup_error = e
            self.ready.set()
            return
        self.ready.set()
        self._t0 = oclock.monotonic()
        if self.tracer.enabled:
            watch_gc(self.tracer)
        try:
            while not self._stop.is_set():
                drained = self._drain_inbox()
                if self.sched.has_work:
                    self._step()
                elif not drained:
                    # one span per stretch with no work
                    with self.tracer.engine("eng.wait"):
                        job = self._next_job()
                    if job is not None:
                        self._start_job(job)
        finally:
            unwatch_gc(self.tracer)
        self._fail_all("gateway shutting down")

    def _step(self) -> None:
        """One scheduler iteration and its token publication, as one
        ``eng.step`` span (a profiler step)."""
        sched = self.sched
        traced = self.tracer.enabled
        if traced:
            queued = len(sched.queue)
            busy = sum(not s.free for s in sched.slots)
        with self.tracer.engine("eng.step",
                                step_num=self._n_steps) as sp:
            self._n_steps += 1
            try:
                sched.step()
            except Exception as e:        # a broken step fails every
                self._fail_all(repr(e))   # live request, not the
                return                    # whole gateway
            self._publish()
            if traced:
                admitted = queued - len(sched.queue)
                sp.set(admitted=admitted, active=busy + admitted)

    def _next_job(self) -> Optional[GatewayJob]:
        """Block until a job arrives (``None`` once stopping)."""
        while not self._stop.is_set():
            try:
                return self.inbox.get(timeout=0.05)
            except queue.Empty:
                continue
        return None

    def _drain_inbox(self) -> bool:
        drained = False
        while True:
            try:
                job = self.inbox.get_nowait()
            except queue.Empty:
                return drained
            self._start_job(job)
            drained = True

    def _start_job(self, job: GatewayJob) -> None:
        try:
            segs = job.segments
            n = len(segs.token_ids)
            pctx = getattr(job.span, "ctx", None)
            waited = max(oclock.monotonic() - job.enqueued_t, 0.0)
            self._m_inbox.observe(waited)
            if pctx is not None:
                self.tracer.add("gw.inbox_wait", waited, parent=pctx,
                                t0=job.enqueued_t)
            cache1, matched, logits, served = None, 0, None, ""
            if self.fetcher is not None:
                rs = (self.tracer.start("gw.resolve", parent=pctx,
                                        attrs={"prompt_tokens": n})
                      if pctx is not None else NULL_SPAN)
                # ambient: attempt spans nest here, and the request's
                # remaining latency budget (wire extension field
                # `deadline_s`) scopes the whole resolve — the planner
                # prunes against it and the peers see the remainder
                with rs, deadline_scope(job.parsed.deadline_s):
                    with phase("catalog.sync"):
                        self.fetcher.sync()
                    cache1, matched, logits, served = \
                        self.fetcher.resolve(segs)
                    rs.set(matched=matched, served_by=served)
            req = Request(
                tokens=np.asarray(segs.token_ids, np.int32),
                max_new_tokens=job.parsed.max_tokens,
                tenant=job.parsed.tenant,
                cache1=cache1, n_prefix=matched,
                trace=pctx,
                # prefix logits only mean "skip prefill entirely" on a
                # FULL hit; a partial hit resumes from `matched` and
                # recomputes the suffix
                prefix_logits=(logits if matched == n
                               and logits is not None else None))
            rid = self.sched.submit(req)
            if pctx is not None:
                # the request id doubles as a trace lookup key
                self.tracer.alias(job.rid, pctx.trace_id)
            job.matched, job.served_by = matched, served
            if self.fetcher is not None:
                job.decision, job.dedup_of, job.fetch_s = \
                    self.fetcher.last_decision
                if job.decision is not None:
                    # the request id also resolves the decision record
                    # (GET /v1/decisions/cmpl-N)
                    LEDGER.alias(job.rid, job.decision["id"])
            self._live[rid] = [job, req, 0]
        except Exception as e:
            if self.admission is not None:
                self.admission.release(job.parsed.tenant)
            job.push(("error", repr(e)))

    def _on_prefill(self, slot_i: int, req: Request, logits_row) -> None:
        """Fresh prefill = complete cache miss: publish its ranges.
        Timed as ``gw.upload`` in the miss's trace."""
        if self.fetcher is None or not self.policy.upload_on_miss:
            return
        entry = self._live.get(req.req_id)
        if entry is None or entry[0].matched:
            return
        pctx = getattr(entry[0].span, "ctx", None)
        up = (self.tracer.start("gw.upload", parent=pctx)
              if pctx is not None else NULL_SPAN)
        # the longest range any key of this prompt can have: the slot is
        # pulled to the host only that far (its bucket)
        n_positions = self.model.cache_len(len(entry[0].segments.token_ids))
        try:
            with up:
                cache1 = self.engine.slot_cache(slot_i, n_positions)
                up.set(pulled_positions=self.engine.pull_positions(
                           n_positions),
                       packed_bytes=self.fetcher.upload(
                           entry[0].segments, cache1, logits_row[None]))
        except Exception as e:
            self.fetcher.stats["upload_errors"] += 1
            FLIGHT.record("upload.failed", client="gateway", error=repr(e))

    def _publish(self) -> None:
        finished = []
        for rid, entry in self._live.items():
            job, req, _sent = entry
            toks = req.stats.output_tokens
            while entry[2] < len(toks):
                job.push(("token", int(toks[entry[2]])))
                entry[2] += 1
            if req.stats.finish_t:
                lat = req.stats.finish_t - req.stats.submit_t
                if self.admission is not None:
                    self.admission.release(job.parsed.tenant, lat)
                self._m_ttft.observe(req.stats.ttft)
                self._m_latency.observe(lat)
                self._commit_decision(job, req, lat)
                job.push(("done", req.stats.finish_reason,
                          {"matched_tokens": job.matched,
                           "served_by": job.served_by,
                           "ttft_s": req.stats.ttft,
                           "latency_s": lat,
                           "trace_id": getattr(job.span, "trace_id",
                                               "")}))
                finished.append(rid)
        for rid in finished:
            del self._live[rid]

    def _commit_decision(self, job: GatewayJob, req: Request,
                         lat: float) -> None:
        """Close the job's decision record with the realized outcome.

        Deferred to finish because the gateway's planner runs without a
        PerfModel (``local_est_s`` is None): the counterfactual
        baseline is the ledger's *learned* per-token prefill rate, fed
        here from every complete-miss request's measured wall prefill
        (admit -> first token)."""
        rec = job.decision
        if rec is None:
            return
        st = req.stats
        first = st.first_token_t or st.finish_t
        prefill_s = max(first - st.admit_t, 0.0) if st.admit_t else 0.0
        n = st.prompt_tokens
        if job.matched > 0:
            LEDGER.commit(
                rec, chosen=job.served_by or None,
                result="hit" if job.matched >= n else "partial",
                fetch_s=job.fetch_s,
                suffix_s=prefill_s if job.matched < n else 0.0,
                dedup_of=job.dedup_of,
                ttft_s=st.ttft, latency_s=lat)
        else:
            LEDGER.note_prefill(n, prefill_s)
            LEDGER.commit(rec, chosen=None, result="local",
                          local_prefill_s=prefill_s,
                          ttft_s=st.ttft, latency_s=lat)

    def _fail_all(self, message: str) -> None:
        for rid, (job, _req, _sent) in list(self._live.items()):
            if self.admission is not None:
                self.admission.release(job.parsed.tenant)
            job.push(("error", message))
        self._live.clear()

    # ------------------------------------------------------------------
    def report(self) -> ServingReport:
        """Cluster-wide serving report: completed-request percentiles
        per tenant, shed counts from admission, per-peer fabric stats —
        the same vocabulary as the SessionPool benchmarks."""
        reqs = [r.stats for r in self.sched.done] \
            if self.sched is not None else []
        wall = (oclock.monotonic() - self._t0) if self._t0 else 0.0
        shed = self.admission.shed_counts() \
            if self.admission is not None else {}
        per_peer = self.fetcher.peer_stats() \
            if self.fetcher is not None else {}
        return ServingReport.from_requests(reqs, wall,
                                           per_peer=per_peer, shed=shed)
