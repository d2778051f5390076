"""Prompt-cache state (de)serialization — llama_state_{get,set}_data analogue.

A *state blob* is the transferable artifact of the paper: the per-layer
KV/latent/SSM cache truncated to the prompt prefix, plus the last-token
logits (so a full hit needs no model execution at all), plus integrity
metadata.

Two wire formats coexist:

* **v2 (single-frame)** — one msgpack payload, optionally compressed,
  with a 3-byte codec tag (``ZST`` zstandard / ``ZLB`` zlib / ``RAW``
  none). Produced by :func:`extract_state`; every v2 blob already
  stored on a peer stays readable forever (``parse_state`` and
  :class:`ChunkedRestorer` both accept it).

* **v3 (chunked)** — a header chunk (manifest + per-chunk integrity
  digests) followed by per-layer-group data chunks, each compressed
  independently so a consumer can decode chunk *i* while chunk *i+1*
  is still on the wire. Sequence-axis leaves are additionally cut at
  the prompt-range boundaries, so :func:`extract_state_ranges` can
  serialize the **longest** range once and emit every shorter range as
  a header rewrite over a prefix of the already-encoded chunks — a
  miss upload costs ONE serialization pass, not ``max_ranges``. Leaf
  buffers are handed to msgpack as ``memoryview`` s (zero-copy bin
  encoding: no ``tobytes()`` staging duplicates). At rest the chunk
  sequence travels as one container (:mod:`repro.core.chunkfmt`);
  in flight the ``get_chunks`` op streams one frame per chunk and
  :class:`ChunkedRestorer` consumes them incrementally — the engine's
  layer-streamed suffix prefill starts as soon as the layer groups it
  needs have landed (see ``InferenceEngine.resume_streamed``).

Sequence-sliceable leaves (``k``, ``v``, ``ckv``, ``krope``) are truncated
to the prefix length; state-like leaves (``conv``, ``ssd``, ``cross_k``,
``cross_v``) ship whole. Ring-buffer (sliding-window) caches ship whole
once wrapped — their slot layout is position-consistent because restore
resumes at the same absolute offset.
"""
from __future__ import annotations

import hashlib
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import msgpack
import numpy as np

try:                                   # optional [edge] extra
    import zstandard as zstd
except ImportError:                    # pragma: no cover - env dependent
    zstd = None

import jax
import jax.numpy as jnp

from repro.core.chunkfmt import (  # noqa: F401  (re-exported)
    CHUNK_MAGIC, is_chunked, pack_container, split_container,
)
# ambient tracing: serialize/restore/per-chunk-digest phases appear in
# a request's span tree whenever the calling thread holds an active
# span (no-ops otherwise — the sim hot path pays one getattr)
from repro.obs.trace import phase

SEQ_LEAVES = {"k", "v", "ckv", "krope"}
FORMAT_VERSION = 2                     # single-frame payload version
CHUNK_VERSION = 3                      # chunked (streaming) format version
_CHUNK_DIGEST_BYTES = 12

# serialization-pass accounting: incremented once per full walk over the
# cache tree (extract_state, and ONE increment for a whole
# extract_state_ranges call regardless of how many ranges it emits).
# Benchmarks/tests assert on this to pin the single-pass upload contract.
STATS = {"serialize_passes": 0}

# int8 per-channel quantization (CacheGen-style, beyond-paper): halves the
# transferable blob vs bf16/zstd, shifting the paper's break-even point
# toward caching. Applied to the large seq-axis leaves only; SSM states
# (fp32, dynamics-critical) ship unquantized.
QUANT_LEAVES = {"k", "v", "ckv", "krope", "cross_k", "cross_v"}


class ChunkError(ValueError):
    """A v3 chunk stream violated its manifest: bad version/meta hash,
    out-of-order or truncated chunk, integrity digest mismatch. The
    stream can no longer be trusted — consumers abandon the fetch and
    fall back (next attempt, then local prefill); never a hang."""


def _quantize(arr: np.ndarray):
    """Symmetric int8 over the last axis. Returns (q, scale fp16).
    Scales are per last-axis row, so a seq-axis prefix slice of (q,
    scale) equals quantizing the prefix directly — which is what lets
    range uploads share quantized chunk bytes."""
    a = arr.astype(np.float32)
    scale = np.max(np.abs(a), axis=-1, keepdims=True) / 127.0
    scale = np.where(scale == 0, 1.0, scale)
    q = np.clip(np.round(a / scale), -127, 127).astype(np.int8)
    return q, scale.astype(np.float16)


def _dequantize(q: np.ndarray, scale: np.ndarray, dtype) -> np.ndarray:
    return (q.astype(np.float32) * scale.astype(np.float32)).astype(dtype)


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, "key", getattr(last, "name", str(last)))


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _seg_key(path_str: str) -> str:
    """Layer-streaming group key: the model *segment* a leaf belongs to
    ('segments/0/attn/k' -> 'segments/0'; 'dec/k' -> 'dec'). Leaves of
    one segment share the leading layer axis, and the engine consumes
    restored chunks one (segment, layer-range) group at a time."""
    parts = path_str.split("/")
    if parts[0] == "segments" and len(parts) > 2:
        return "/".join(parts[:2])
    return parts[0]


def default_codec() -> str:
    """Best available compression codec for state blobs."""
    return "zstd" if zstd is not None else "zlib"


def _compress(raw, codec: str, level: int) -> bytes:
    if codec == "auto":
        codec = default_codec()
    if codec == "zstd":
        if zstd is None:
            raise RuntimeError(
                "zstd codec requested but zstandard is not installed "
                "(pip install '.[edge]'); use codec='zlib' or 'auto'")
        return b"ZST" + zstd.ZstdCompressor(level=level).compress(raw)
    if codec == "zlib":
        return b"ZLB" + zlib.compress(raw, min(max(level, 1), 9))
    raise ValueError(f"unknown codec {codec!r}")


def _decompress(blob: bytes) -> bytes:
    tag, body = bytes(blob[:3]), blob[3:]
    if tag == b"ZST":
        if zstd is None:
            raise RuntimeError(
                "blob is zstd-compressed but zstandard is not installed "
                "(pip install '.[edge]')")
        return zstd.ZstdDecompressor().decompress(body)
    if tag == b"ZLB":
        return zlib.decompress(body)
    if tag == b"RAW":
        return bytes(body)
    raise ValueError("bad state blob tag")


def _buffers(arr: np.ndarray) -> memoryview:
    """A zero-copy byte view of ``arr`` for msgpack bin encoding. The
    caller guarantees C-contiguity (ascontiguousarray on slices is the
    single staging buffer; no additional ``tobytes()`` duplicate).
    Viewed through uint8 first: numpy exports no buffer for bfloat16."""
    return memoryview(arr.reshape(-1).view(np.uint8))


# ---------------------------------------------------------------------------
# v2: single-frame blobs (kept verbatim for compat + small states)
# ---------------------------------------------------------------------------

def extract_state(cache, n_eff: int, meta: bytes,
                  logits: Optional[np.ndarray] = None,
                  compress: bool = True, level: int = 1,
                  quantize: bool = False, codec: str = "auto") -> bytes:
    """Serialize ``cache`` truncated to ``n_eff`` positions (v2 single
    frame). ``quantize``: int8 per-channel KV quantization.
    ``codec``: 'auto' (zstd if available, else zlib) | 'zstd' | 'zlib'."""
    STATS["serialize_passes"] += 1
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    out = []
    for path, leaf in leaves:
        arr = np.asarray(leaf)
        name = _leaf_name(path)
        if name in SEQ_LEAVES:
            keep = min(int(n_eff), arr.shape[2])
            arr = arr[:, :, :keep]
        entry = {
            "path": _path_str(path),
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
        }
        if quantize and name in QUANT_LEAVES and arr.ndim >= 3 \
                and arr.dtype != np.int8:
            q, scale = _quantize(arr)
            entry["data"] = _buffers(np.ascontiguousarray(q))
            entry["q_scale"] = _buffers(np.ascontiguousarray(scale))
            entry["q_scale_shape"] = list(scale.shape)
        else:
            entry["data"] = _buffers(np.ascontiguousarray(arr))
        out.append(entry)
    payload = {
        "version": FORMAT_VERSION,
        "meta_hash": hashlib.blake2b(meta, digest_size=16).digest(),
        "n_eff": int(n_eff),
        "logits": (None if logits is None else {
            "shape": list(logits.shape),
            "data": np.asarray(logits, np.float16).tobytes(),
        }),
        "leaves": out,
    }
    raw = msgpack.packb(payload, use_bin_type=True)
    if compress:
        return _compress(raw, codec, level)
    return b"RAW" + raw


# ---------------------------------------------------------------------------
# v3: chunked, range-shared serialization (single pass)
# ---------------------------------------------------------------------------

def extract_state_ranges(cache, n_effs: Sequence[int], meta: bytes,
                         logits: Optional[np.ndarray] = None,
                         compress: bool = True, level: int = 1,
                         quantize: bool = False, codec: str = "auto",
                         chunk_layers: int = 1
                         ) -> Dict[int, List[bytes]]:
    """Traced front door for :func:`_extract_state_ranges`: the whole
    single-pass serialization shows up as one ``state.serialize`` span
    in the calling request's tree."""
    with phase("state.serialize", ranges=len(list(n_effs))):
        return _extract_state_ranges(cache, n_effs, meta, logits=logits,
                                     compress=compress, level=level,
                                     quantize=quantize, codec=codec,
                                     chunk_layers=chunk_layers)


def _extract_state_ranges(cache, n_effs: Sequence[int], meta: bytes,
                          logits: Optional[np.ndarray] = None,
                          compress: bool = True, level: int = 1,
                          quantize: bool = False, codec: str = "auto",
                          chunk_layers: int = 1
                          ) -> Dict[int, List[bytes]]:
    """ONE serialization pass over ``cache``, emitting a chunk list per
    requested prefix length.

    Chunks are keyed (layer-group, seq-band): each leaf is cut along
    its layer axis into groups of ``chunk_layers`` and along its
    sequence axis at the ``n_effs`` boundaries. The longest range owns
    every chunk; each shorter range's list is a fresh (cheap) header
    plus the *same* encoded chunk bytes restricted to its bands — no
    re-extraction, no re-compression. ``logits`` attach to the longest
    range only (the full-prompt blob). Returns ``{n_eff: [header,
    chunk, ...]}``; wrap a list with
    :func:`~repro.core.chunkfmt.pack_container` to store/ship it.
    Timed as two phases: ``upload.to_host`` (every leaf pulled to the
    host, ``nbytes``) and ``upload.encode`` (the rest)."""
    bounds = sorted({int(n) for n in n_effs})
    if not bounds:
        raise ValueError("need at least one range length")
    STATS["serialize_passes"] += 1
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    with phase("upload.to_host") as sp:
        host = [(path, np.asarray(leaf)) for path, leaf in leaves]
        sp.set(nbytes=sum(a.nbytes for _, a in host))
    with phase("upload.encode"):
        return _encode_state_ranges(host, bounds, meta, logits, compress,
                                    level, quantize, codec, chunk_layers)


def _encode_state_ranges(leaves, bounds: List[int], meta: bytes,
                         logits: Optional[np.ndarray], compress: bool,
                         level: int, quantize: bool, codec: str,
                         chunk_layers: int) -> Dict[int, List[bytes]]:
    """The host half of :func:`_extract_state_ranges`: cut, quantize,
    compress and frame ``leaves`` (``(path, host array)`` pairs)."""
    n_max = bounds[-1]
    meta_hash = hashlib.blake2b(meta, digest_size=16).digest()
    # group leaves by segment prefix, preserving tree (= compute) order
    seg_order: List[str] = []
    by_seg: Dict[str, list] = {}
    for path, arr in leaves:
        ps = _path_str(path)
        sk = _seg_key(ps)
        if sk not in by_seg:
            by_seg[sk] = []
            seg_order.append(sk)
        by_seg[sk].append((ps, _leaf_name(path), arr))

    # data chunks in stream order: (segment, layer-group) major so the
    # consumer can run layers [lo:hi) the moment their bands are in,
    # seq-band minor so every range is a prefix of the chunk sequence
    # per group. Each chunk: manifest entry + one compressed body.
    manifests: List[dict] = []
    bodies: List[bytes] = []
    for sk in seg_order:
        entries = by_seg[sk]
        n_layers = entries[0][2].shape[0]
        step = max(int(chunk_layers), 1)
        prepared = []
        for ps, name, arr in entries:
            if name in SEQ_LEAVES:
                keep = min(n_max, arr.shape[2])
                arr = arr[:, :, :keep]
                # per-leaf band edges: global range boundaries clipped
                # to this leaf's (possibly windowed) capacity
                edges = sorted({min(b, keep) for b in bounds})
                cuts = [0] + edges
            else:
                cuts = None            # whole-leaf, band 0 only
            q = quantize and name in QUANT_LEAVES and arr.ndim >= 3 \
                and arr.dtype != np.int8
            if q:
                qa, scale = _quantize(arr)
            else:
                qa, scale = arr, None
            prepared.append((ps, name, qa, scale, cuts, str(arr.dtype)))
        for lo in range(0, n_layers, step):
            hi = min(lo + step, n_layers)
            for band in range(len(bounds)):
                pieces, bufs = [], []
                for ps, name, qa, scale, cuts, dt in prepared:
                    if cuts is None:
                        if band:
                            continue   # state leaves ride band 0
                        b0, b1 = None, None
                        piece = qa[lo:hi]
                        sp = scale[lo:hi] if scale is not None else None
                    else:
                        if band + 1 >= len(cuts):
                            continue   # leaf capacity already covered
                        b0, b1 = cuts[band], cuts[band + 1]
                        if b1 <= b0:
                            continue
                        piece = qa[lo:hi, :, b0:b1]
                        sp = scale[lo:hi, :, b0:b1] \
                            if scale is not None else None
                    piece = np.ascontiguousarray(piece)
                    ent = {"path": ps, "shape": list(piece.shape),
                           "dtype": dt, "off": 0 if b0 is None else b0}
                    bufs.append(_buffers(piece))
                    if sp is not None:
                        sp = np.ascontiguousarray(sp)
                        ent["q_scale_shape"] = list(sp.shape)
                        bufs.append(_buffers(sp))
                    pieces.append(ent)
                if not pieces:
                    continue
                raw = msgpack.packb(bufs, use_bin_type=True)
                body = _compress(raw, codec, level) if compress \
                    else b"RAW" + raw
                manifests.append({
                    "seg": sk, "lo": lo, "hi": hi, "band": band,
                    "nbytes": len(body),
                    "digest": hashlib.blake2b(
                        body, digest_size=_CHUNK_DIGEST_BYTES).digest(),
                    "pieces": pieces,
                })
                bodies.append(body)

    def header(n_eff: int, with_logits: bool, idx: List[int]) -> bytes:
        hdr = {
            "version": CHUNK_VERSION,
            "meta_hash": meta_hash,
            "n_eff": int(n_eff),
            "n_chunks": len(idx),
            "logits": (None if (logits is None or not with_logits) else {
                "shape": list(logits.shape),
                "data": np.asarray(logits, np.float16).tobytes(),
            }),
            "chunks": [manifests[i] for i in idx],
        }
        raw = msgpack.packb(hdr, use_bin_type=True)
        return _compress(raw, codec, level) if compress else b"RAW" + raw

    out: Dict[int, List[bytes]] = {}
    for bi, n_eff in enumerate(bounds):
        # bands above bi carry positions beyond this range's prefix:
        # the delta manifest simply leaves them out
        idx = [i for i, m in enumerate(manifests) if m["band"] <= bi]
        out[n_eff] = [header(n_eff, n_eff == n_max, idx)] + \
            [bodies[i] for i in idx]
    return out


def extract_state_chunks(cache, n_eff: int, meta: bytes,
                         logits: Optional[np.ndarray] = None,
                         **kw) -> List[bytes]:
    """Chunked serialization of one prefix length (v3). See
    :func:`extract_state_ranges` for the multi-range single-pass form."""
    return extract_state_ranges(cache, [n_eff], meta, logits=logits,
                                **kw)[int(n_eff)]


# ---------------------------------------------------------------------------
# restore
# ---------------------------------------------------------------------------

class ChunkedRestorer:
    """Incremental consumer of a v3 chunk stream.

    Feed chunks in wire order with :meth:`feed`; it validates the
    header (version + model meta hash) and every data chunk's size and
    integrity digest against the manifest, raising :class:`ChunkError`
    the moment the stream lies — a corrupt/truncated stream costs one
    bounded error, never a hang or a silently wrong cache.

    ``feed`` returns the layer groups ``(seg, lo, hi)`` completed by
    that chunk, in stream (= compute) order, which is what the engine's
    layer-streamed resume blocks on. :meth:`group_cache` assembles one
    group's leaves into template-shaped numpy buffers (preallocated
    zeros + slice writes: one staging buffer per leaf slice, no
    device→host template copy); :meth:`result` assembles the whole
    cache for non-streamed consumers.

    A v2 single-frame blob fed as the only chunk is recognized and
    handled (:attr:`v2_payload`): the mixed-version-fleet path, where a
    v3 client streams from a peer that still holds v2 blobs.
    """

    def __init__(self, meta: bytes):
        self.meta = meta
        self.header: Optional[dict] = None
        self.v2_payload: Optional[dict] = None
        self.fed = 0
        self.bytes_fed = 0
        self._chunks: List[bytes] = []          # raw, for re-packing
        self._pieces: Dict[Tuple[str, int, int], list] = {}
        self._order: List[Tuple[str, int, int]] = []
        self._remaining: Dict[Tuple[str, int, int], int] = {}
        # template flatten memo: group_cache runs once per layer group
        # on the TTFT-critical streamed path — flatten the template
        # pytree once, not once per group
        self._tmpl_memo: Tuple[int, Optional[Dict[str, Any]]] = (0, None)

    # -- stream ingestion ----------------------------------------------
    def feed(self, chunk: bytes) -> List[Tuple[str, int, int]]:
        chunk = bytes(chunk)
        if self.fed == 0:
            self._feed_header(chunk)
            self.fed = 1
            self.bytes_fed += len(chunk)
            self._chunks.append(chunk)
            return []
        if self.v2_payload is not None:
            raise ChunkError("trailing chunk after a v2 single-frame blob")
        if self.header is None or self.fed > self.header["n_chunks"]:
            raise ChunkError("chunk beyond the manifest's n_chunks")
        man = self.header["chunks"][self.fed - 1]
        with phase("chunk.verify", chunk=self.fed, nbytes=len(chunk)):
            if len(chunk) != man["nbytes"]:
                raise ChunkError(
                    f"chunk {self.fed} size {len(chunk)} != manifest "
                    f"{man['nbytes']} (truncated/corrupt stream)")
            got = hashlib.blake2b(
                chunk, digest_size=_CHUNK_DIGEST_BYTES).digest()
            if got != bytes(man["digest"]):
                raise ChunkError(
                    f"chunk {self.fed} integrity digest mismatch")
            try:
                bufs = msgpack.unpackb(_decompress(chunk), raw=False)
                arrs = self._decode_pieces(man["pieces"], bufs)
            except ChunkError:
                raise
            except Exception as e:
                raise ChunkError(
                    f"undecodable chunk {self.fed}: {e!r}") from e
        gid = (man["seg"], int(man["lo"]), int(man["hi"]))
        self._pieces.setdefault(gid, []).extend(arrs)
        self._remaining[gid] -= 1
        self.fed += 1
        self.bytes_fed += len(chunk)
        self._chunks.append(chunk)
        done = []
        # groups complete strictly in stream order; pop every leading
        # group that just finished
        while self._order and self._remaining[self._order[0]] == 0:
            done.append(self._order.pop(0))
        return done

    def _feed_header(self, chunk: bytes) -> None:
        try:
            payload = msgpack.unpackb(_decompress(chunk), raw=False)
        except Exception as e:
            raise ChunkError(f"undecodable header chunk: {e!r}") from e
        if not isinstance(payload, dict):
            raise ChunkError("header chunk is not a map")
        version = payload.get("version")
        want = hashlib.blake2b(self.meta, digest_size=16).digest()
        if bytes(payload.get("meta_hash", b"")) != want:
            raise ValueError("state blob was produced by a different "
                             "model configuration (integrity check "
                             "failed)")
        if version == FORMAT_VERSION:      # v2 blob as a 1-chunk stream
            self.v2_payload = payload
            return
        if version != CHUNK_VERSION:
            raise ChunkError(f"unsupported chunk-stream version "
                             f"{version!r}")
        if not isinstance(payload.get("chunks"), list) or \
                payload.get("n_chunks") != len(payload["chunks"]):
            raise ChunkError("header manifest inconsistent with n_chunks")
        self.header = payload
        for man in payload["chunks"]:
            gid = (man["seg"], int(man["lo"]), int(man["hi"]))
            if gid not in self._remaining:
                self._remaining[gid] = 0
                self._order.append(gid)
            self._remaining[gid] += 1

    @staticmethod
    def _decode_pieces(manifest_pieces: list, bufs: list) -> list:
        out, bi = [], 0
        for ent in manifest_pieces:
            if bi >= len(bufs):
                raise ChunkError("chunk body has fewer buffers than "
                                 "its manifest")
            if "q_scale_shape" in ent:
                # quantized piece: int8 data + fp16 per-row scales;
                # ent["dtype"] is the restore target
                q = np.frombuffer(bufs[bi], np.int8).reshape(ent["shape"])
                scale = np.frombuffer(bufs[bi + 1], np.float16).reshape(
                    ent["q_scale_shape"])
                bi += 2
                arr = _dequantize(q, scale, np.dtype(ent["dtype"]))
            else:
                arr = np.frombuffer(bufs[bi], dtype=ent["dtype"]).reshape(
                    ent["shape"])
                bi += 1
            out.append((ent["path"], int(ent.get("off", 0)), arr))
        return out

    # -- state ----------------------------------------------------------
    @property
    def complete(self) -> bool:
        if self.v2_payload is not None:
            return True
        return self.header is not None and \
            self.fed == self.header["n_chunks"] + 1

    @property
    def n_eff(self) -> int:
        src = self.v2_payload or self.header
        if src is None:
            raise ChunkError("no header chunk fed yet")
        return int(src["n_eff"])

    def logits(self) -> Optional[np.ndarray]:
        src = self.v2_payload or self.header or {}
        lg = src.get("logits")
        if not lg:
            return None
        return np.frombuffer(lg["data"], np.float16).reshape(
            lg["shape"]).astype(np.float32)

    def raw_chunks(self) -> List[bytes]:
        """The chunks as fed — re-pack with ``pack_container`` to cache
        or re-ship the blob without another serialization pass."""
        return list(self._chunks)

    # -- assembly -------------------------------------------------------
    def _template_index(self, template) -> Dict[str, Any]:
        """path-string -> leaf map of ``template``, memoized (a restorer
        serves one fetch, so one template)."""
        if self._tmpl_memo[0] == id(template):
            return self._tmpl_memo[1]
        idx = {_path_str(path): leaf for path, leaf in
               jax.tree_util.tree_flatten_with_path(template)[0]}
        self._tmpl_memo = (id(template), idx)
        return idx

    def group_cache(self, gid: Tuple[str, int, int], template):
        """Template-shaped numpy leaves for layer group ``gid``:
        ``{leaf_name: np[hi-lo, ...]}`` with the stored prefix written
        into preallocated zero buffers (ring/SSM leaves land whole).
        The engine runs layers [lo:hi) of the suffix on exactly this."""
        seg, lo, hi = gid
        out = {}
        for ps, leaf in self._template_index(template).items():
            if _seg_key(ps) != seg:
                continue
            shape = (hi - lo,) + tuple(leaf.shape[1:])
            out[ps] = np.zeros(shape, dtype=leaf.dtype)
        for ps, off, arr in self._pieces.get(gid, []):
            buf = out.get(ps)
            if buf is None:
                raise ChunkError(f"blob leaf {ps} not in the restore "
                                 f"template")
            self._place(buf, off, arr, ps)
        return out

    @staticmethod
    def _place(buf: np.ndarray, off: int, arr: np.ndarray,
               ps: str) -> None:
        if arr.shape == buf.shape and off == 0:
            buf[...] = arr
            return
        name = ps.rsplit("/", 1)[-1]
        if name not in SEQ_LEAVES:
            raise ChunkError(f"shape mismatch on state leaf {ps}: "
                             f"{arr.shape} vs {buf.shape}")
        end = off + arr.shape[2]
        if end > buf.shape[2] or arr.shape[:2] != buf.shape[:2] \
                or arr.shape[3:] != buf.shape[3:]:
            raise ChunkError(
                f"stored prefix exceeds engine cache on {ps}: "
                f"{arr.shape}@{off} vs {buf.shape}")
        buf[:, :, off:end] = arr

    def group_tree(self, gid: Tuple[str, int, int], template):
        """Like :meth:`group_cache` but returned as the pytree matching
        ``template``'s segment subtree sliced to layers [lo:hi] —
        directly consumable by ``InferenceEngine.resume_streamed``."""
        seg = gid[0]
        sub = template
        for part in seg.split("/"):
            sub = sub[int(part)] if part.isdigit() else sub[part]
        gnp = self.group_cache(gid, template)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(sub)
        new = [gnp[seg + "/" + _path_str(path)] for path, _ in leaves]
        return jax.tree_util.tree_unflatten(treedef, new)

    def result(self, template):
        """Assemble the whole cache (non-streamed path). Returns
        ``(cache, n_eff, logits|None)``; raises on incomplete streams
        or manifest/template coverage mismatches."""
        if self.v2_payload is not None:
            return restore_state(self.v2_payload, template)
        if not self.complete:
            raise ChunkError(
                f"chunk stream incomplete ({self.fed - 1}/"
                f"{0 if self.header is None else self.header['n_chunks']}"
                f" data chunks)")
        leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
        # per-leaf preallocated host buffer, pieces written in place,
        # ONE host->device transfer per leaf — no np.array(template)
        # round trip, no per-piece device copies
        bufs: Dict[str, np.ndarray] = {}
        for path, leaf in leaves:
            bufs[_path_str(path)] = np.zeros(leaf.shape, leaf.dtype)
        covered = set()
        for gid in self._pieces:
            seg, lo, hi = gid
            for ps, off, arr in self._pieces[gid]:
                buf = bufs.get(ps)
                if buf is None:
                    raise ChunkError(f"blob leaf {ps} not in template")
                self._place(buf[lo:hi], off, arr, ps)
                covered.add(ps)
        missing = set(bufs) - covered
        if missing:
            raise ChunkError(f"blob missing leaves {sorted(missing)}")
        new_leaves = [jnp.asarray(bufs[_path_str(path)])
                      for path, _ in leaves]
        cache = jax.tree_util.tree_unflatten(treedef, new_leaves)
        return cache, self.n_eff, self.logits()


def parse_state(blob: bytes, meta: bytes) -> Dict[str, Any]:
    """Decode a state blob (either format) into a payload for
    :func:`restore_state`. v3 containers decode through a
    :class:`ChunkedRestorer`, so both formats share one validation and
    placement path. Shows up as a ``state.parse`` span (with nested
    ``chunk.verify`` phases for v3) in the calling request's tree."""
    with phase("state.parse", nbytes=len(blob)):
        return _parse_state(blob, meta)


def _parse_state(blob: bytes, meta: bytes) -> Dict[str, Any]:
    if is_chunked(blob):
        r = ChunkedRestorer(meta)
        for c in split_container(blob):
            r.feed(c)
        if r.v2_payload is not None:
            return r.v2_payload
        if not r.complete:
            raise ChunkError("container holds an incomplete chunk stream")
        return {"version": CHUNK_VERSION, "n_eff": r.n_eff,
                "_restorer": r}
    body = _decompress(blob)
    payload = msgpack.unpackb(body, raw=False)
    if payload["version"] != FORMAT_VERSION:
        raise ValueError("state blob version mismatch")
    want = hashlib.blake2b(meta, digest_size=16).digest()
    if payload["meta_hash"] != want:
        raise ValueError("state blob was produced by a different model "
                         "configuration (integrity check failed)")
    return payload


def restore_state(payload: Dict[str, Any], template) -> Tuple[Any, int,
                                                              Optional[np.ndarray]]:
    """Place stored leaves into ``template`` (a freshly-initialized cache of
    the engine's max_len). Returns (cache, n_eff, logits|None).

    Partial-prefix seq leaves are written into the template on-device
    via ``jax.lax.dynamic_update_slice`` — no host copy of the template
    and no full-leaf rewrite (the old ``np.array(template)`` +
    full-assign path doubled every leaf through host memory).

    Shows up as a ``state.restore`` span in the calling request's
    tree."""
    with phase("state.restore", n_eff=int(payload.get("n_eff", 0))):
        return _restore_state(payload, template)


def _restore_state(payload: Dict[str, Any], template
                   ) -> Tuple[Any, int, Optional[np.ndarray]]:
    if "_restorer" in payload:
        return payload["_restorer"].result(template)
    stored = {d["path"]: d for d in payload["leaves"]}
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    new_leaves = []
    for path, leaf in leaves:
        d = stored.get(_path_str(path))
        if d is None:
            raise ValueError(f"blob missing leaf {_path_str(path)}")
        if "q_scale" in d:
            q = np.frombuffer(d["data"], np.int8).reshape(d["shape"])
            scale = np.frombuffer(d["q_scale"], np.float16).reshape(
                d["q_scale_shape"])
            arr = _dequantize(q, scale, np.dtype(d["dtype"]))
        else:
            arr = np.frombuffer(d["data"],
                                dtype=d["dtype"]).reshape(d["shape"])
        tl_shape = tuple(leaf.shape)
        if arr.shape != tl_shape:
            if _leaf_name(path) not in SEQ_LEAVES:
                raise ValueError(f"shape mismatch on {_path_str(path)}")
            if arr.shape[2] > tl_shape[2] or arr.shape[:2] != tl_shape[:2] \
                    or arr.shape[3:] != tl_shape[3:]:
                raise ValueError(
                    f"stored prefix longer than engine cache on "
                    f"{_path_str(path)}: {arr.shape} vs {tl_shape}")
            new_leaves.append(jax.lax.dynamic_update_slice(
                jnp.asarray(leaf),
                jnp.asarray(arr).astype(leaf.dtype),
                (0,) * len(tl_shape)))
        else:
            new_leaves.append(jnp.asarray(arr))
    cache = jax.tree_util.tree_unflatten(treedef, new_leaves)
    logits = None
    if payload.get("logits"):
        lg = payload["logits"]
        logits = np.frombuffer(lg["data"], np.float16).reshape(lg["shape"])
        logits = logits.astype(np.float32)
    return cache, int(payload["n_eff"]), logits


def state_nbytes(blob: bytes) -> int:
    return len(blob)
