"""Open-loop HTTP load from one thread: every request is sent when it is
DUE, whatever the server is doing, over a connection of its own, and each
streamed chunk is stamped as it arrives. Timing is by `time.perf_counter`
relative to the moment the window opens (`t_zero`)."""

from __future__ import annotations

import asyncio
import json
import time
from typing import Dict, List


async def _read_chunked(reader, on_chunk) -> None:
    while True:
        size = int((await reader.readline()).strip() or b"0", 16)
        if size == 0:
            await reader.readline()
            return
        data = await reader.readexactly(size + 2)
        on_chunk(data[:-2])


async def post(host: str, port: int, path: str, payload, on_chunk=None,
               timeout_s: float = 300.0):
    """One POST. Streams call `on_chunk(bytes)` per chunk and return the
    status; plain calls return (status, parsed JSON body)."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        body = json.dumps(payload).encode()
        sep = "&" if "?" in path else "?"
        writer.write((f"POST {path}{sep}timeout_s={timeout_s:g} HTTP/1.1\r\n"
                      f"Host: {host}\r\nContent-Type: application/json\r\n"
                      f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
                      ).encode() + body)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        headers = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode("latin1").partition(":")
            headers[k.strip().lower()] = v.strip()
        if headers.get("transfer-encoding") == "chunked":
            chunks: List[bytes] = []
            await _read_chunked(reader, on_chunk or chunks.append)
            return status if on_chunk else (status, b"".join(chunks))
        raw = await reader.read()
        return status, (json.loads(raw) if raw else None)
    finally:
        writer.close()


async def _one(host, port, path, req: Dict, t_zero: float, row: Dict) -> None:
    delay = t_zero + req["due_s"] - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    row["sent_s"] = time.perf_counter() - t_zero
    row["sent_wall"] = time.time()

    def on_chunk(data: bytes) -> None:
        now = time.perf_counter() - t_zero
        for piece in data.split(b"\n"):
            if not piece:
                continue
            item = json.loads(piece)
            if isinstance(item, dict):  # the proxy's typed error chunk
                row["error"] = item
            else:
                row["arrivals_s"].append(now)
                row["tokens"].append(item)

    try:
        row["status"] = await post(
            host, port, path, {"rid": req["i"], "prompt": req["prompt"],
                               "max_new_tokens": req["max_new_tokens"]},
            on_chunk, timeout_s=req["timeout_s"])
    except Exception as e:  # refused, reset, timed out: a failed request
        row["error"] = {"error": repr(e)}


async def run_schedule(host: str, port: int, path: str, schedule: List[Dict],
                       t_zero: float) -> List[Dict]:
    """Send every request of `schedule` at its due time; returns one row per
    request (due_s, sent_s, arrivals_s, tokens, status, error)."""
    rows = [{"i": r["i"], "due_s": r["due_s"], "prompt_len": len(r["prompt"]),
             "max_new_tokens": r["max_new_tokens"], "arrivals_s": [],
             "tokens": [], "status": None, "error": None} for r in schedule]
    tasks = [asyncio.ensure_future(_one(host, port, path, r, t_zero, row))
             for r, row in zip(schedule, rows)]
    await asyncio.gather(*tasks)
    return rows
