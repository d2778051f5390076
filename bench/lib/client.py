"""Open-loop HTTP/SSE client on one asyncio thread.

Each request is sent at its due time on its own connection to
``POST /v1/chat/completions`` with ``"stream": true``; the first
``token_id`` event and ``data: [DONE]`` are timed on the client's clock
(``time.perf_counter``), relative to when the request was DUE, so a
stall also counts against the requests it delays. How late the
generator sent each request is kept apart (``late_s``).
"""
from __future__ import annotations

import asyncio
import json
import time
from typing import List, Optional


class Outcome:
    __slots__ = ("due", "sent", "status", "first", "done", "tokens",
                 "error")

    def __init__(self, due: float):
        self.due = due                  # absolute perf_counter seconds
        self.sent: Optional[float] = None
        self.status: Optional[int] = None
        self.first: Optional[float] = None
        self.done: Optional[float] = None
        self.tokens: List[int] = []
        self.error: Optional[str] = None

    @property
    def late_s(self) -> float:
        return (self.sent - self.due) if self.sent is not None else 0.0


async def _one(host: str, port: int, body: bytes, out: Outcome) -> None:
    delay = out.due - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    out.sent = time.perf_counter()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"POST /v1/chat/completions HTTP/1.1\r\n"
                     b"Host: bench\r\nContent-Type: application/json\r\n"
                     b"Connection: close\r\nContent-Length: "
                     + str(len(body)).encode() + b"\r\n\r\n" + body)
        await writer.drain()
        status = await reader.readline()
        out.status = int(status.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if out.status != 200:
            out.error = (await reader.read())[:200].decode("replace")
            return
        while True:
            line = await reader.readline()
            if not line:
                out.error = out.error or "stream ended before [DONE]"
                return
            if not line.startswith(b"data: "):
                continue
            payload = line[6:].strip()
            if payload == b"[DONE]":
                out.done = time.perf_counter()
                return
            ev = json.loads(payload)
            if "error" in ev:
                out.error = str(ev["error"])[:200]
                continue
            ch = ev["choices"][0]
            if "token_id" in ch:
                if out.first is None:
                    out.first = time.perf_counter()
                out.tokens.append(int(ch["token_id"]))
    except Exception as e:            # noqa: BLE001 - a failed request
        out.error = repr(e)[:200]
    finally:
        if writer is not None:
            writer.close()


async def _run(host, port, bodies, dues, deadline, on_window_end,
               window_end):
    outs = [Outcome(d) for d in dues]
    tasks = [asyncio.ensure_future(_one(host, port, b, o))
             for b, o in zip(bodies, outs)]
    at_end = None
    if on_window_end is not None:
        async def call_at_end():
            await asyncio.sleep(max(window_end - time.perf_counter(), 0.0))
            await asyncio.get_running_loop().run_in_executor(
                None, on_window_end)
        # beside the requests, so a slow callback shortens no drain
        at_end = asyncio.ensure_future(call_at_end())
    timeout = max(deadline - time.perf_counter(), 0.0)
    if tasks:
        _, pending = await asyncio.wait(tasks, timeout=timeout)
        for t in pending:
            t.cancel()
        if pending:
            await asyncio.wait(pending)
    for o in outs:
        if o.done is None and o.error is None:
            o.error = "not finished by the drain limit"
    if at_end is not None:
        await at_end
    return outs


def replay(host: str, port: int, bodies: List[bytes], dues: List[float],
           deadline: float, on_window_end=None,
           window_end: float = 0.0) -> List[Outcome]:
    """Send every body at its due time (absolute perf_counter seconds);
    wait for all until ``deadline``. ``on_window_end`` runs in a worker
    thread at ``window_end`` (the traced run stops its trace), beside
    the requests; the call returns once it and they have ended."""
    return asyncio.run(_run(host, port, bodies, dues, deadline,
                            on_window_end, window_end))
