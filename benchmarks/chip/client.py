"""The seeded load generator: streamed ``POST /v1/chat/completions`` over
loopback, from one asyncio thread in the benchmark's process.

Open loop: request i is due at ``t0 + due_s[i]`` whatever the server does.
Closed loop: ``clients`` coroutines each send, read the whole stream, then
send the next.  Every request records its due time, when its first answer
frame arrived (the first frame after the role frame: a content delta, or the
final frame when the answer is empty), when ``[DONE]`` arrived, the text its
deltas assemble, and the ``robatch`` extension of the final frame.  Times are
``time.perf_counter()`` seconds, the clock every benchmark span uses.
"""
from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass
from typing import Optional


@dataclass
class Sent:
    query_idx: int
    due: float
    sent: float = 0.0
    first: Optional[float] = None
    done: Optional[float] = None
    text: str = ""
    ext: Optional[dict] = None
    error: Optional[str] = None
    late_s: float = 0.0             # how late the generator sent it

    @property
    def answered(self) -> bool:
        return self.done is not None and self.error is None and self.ext is not None


async def _one(port: int, text: str, rec: Sent, timeout_s: float) -> None:
    body = json.dumps({"messages": [{"role": "user", "content": text}],
                       "stream": True}).encode()
    head = (f"POST /v1/chat/completions HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
            f"Content-Type: application/json\r\nContent-Length: {len(body)}\r\n\r\n")
    rec.sent = time.perf_counter()
    rec.late_s = max(0.0, rec.sent - rec.due)
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(head.encode() + body)
        await writer.drain()
        status = await asyncio.wait_for(reader.readline(), timeout_s)
        if b" 200 " not in status:
            rec.error = status.decode(errors="replace").strip()
            return
        while (await asyncio.wait_for(reader.readline(), timeout_s)) not in (b"\r\n", b""):
            pass
        while True:
            line = await asyncio.wait_for(reader.readline(), timeout_s)
            if not line:
                rec.error = rec.error or "stream ended before [DONE]"
                return
            if not line.startswith(b"data: "):
                continue
            payload = line[6:].strip()
            if payload == b"[DONE]":
                rec.done = time.perf_counter()
                return
            frame = json.loads(payload)
            if "error" in frame:
                rec.error = frame["error"].get("message", "error frame")
                continue
            choice = frame["choices"][0]
            if choice["delta"].get("role"):
                continue
            if rec.first is None:
                rec.first = time.perf_counter()
            rec.text += choice["delta"].get("content", "")
            if choice.get("finish_reason") is not None:
                rec.ext = frame.get("robatch")
    except (asyncio.TimeoutError, OSError, ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


async def _open_loop(port, texts, order, due_s, t0, timeout_s):
    recs, tasks = [], []
    for q, due in zip(order, due_s):
        rec = Sent(query_idx=int(q), due=t0 + float(due))
        delay = rec.due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        recs.append(rec)
        tasks.append(asyncio.create_task(_one(port, texts[int(q)], rec, timeout_s)))
    await asyncio.gather(*tasks)
    return recs


async def _closed_loop(port, texts, order, clients, t0, seconds, timeout_s):
    recs: list[Sent] = []
    it = iter(order)
    end = t0 + seconds

    async def worker():
        while time.perf_counter() < end:
            q = next(it)
            rec = Sent(query_idx=int(q), due=time.perf_counter())
            recs.append(rec)
            await _one(port, texts[int(q)], rec, timeout_s)

    await asyncio.gather(*(worker() for _ in range(clients)))
    return recs


def run_load(port: int, texts, order, *, loop: str, seconds: float,
             due_s=None, clients: int = 0, timeout_s: float = 120.0,
             on_start=None) -> tuple[float, list[Sent]]:
    """Drive the window; returns (t0, records).  ``on_start(t0)`` runs just
    before the first request is due (the traced run starts its trace there).
    Requests still in flight at the window's close are waited for."""
    async def main():
        t0 = time.perf_counter() + 0.05
        if on_start is not None:
            on_start(t0)
        if loop == "open":
            recs = await _open_loop(port, texts, order, due_s, t0, timeout_s)
        else:
            recs = await _closed_loop(port, texts, order, clients, t0, seconds, timeout_s)
        return t0, recs

    return asyncio.run(main())
