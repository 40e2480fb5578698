"""The load generator: one process, one event loop. Open loop (requests
sent when due, whatever the server does) and closed loop (each client
sends its next request when the last one is answered). Every streamed
chunk is stamped on arrival; a chunk of eight tokens is eight tokens at
one arrival time. Times are time.monotonic() of this process."""

from __future__ import annotations

import asyncio
import json
import time

import aiohttp

from benchlib import tokenizer

clock = time.monotonic


def new_record(req: dict, due_t: float) -> dict:
    return {"due_t": due_t, "sent_t": None, "done_t": None, "ok": False,
            "measured": bool(req.get("measured")),
            "prompt_tokens": len(req["prompt_ids"]),
            "max_tokens": req["max_tokens"], "ids": [], "token_times": [],
            "finish": None, "error": None, "prompt_ids": req["prompt_ids"]}


async def send(session, base: str, req: dict, rec: dict) -> dict:
    """One streamed greedy completion; fills `rec`."""
    body = {"prompt": tokenizer.text_of(req["prompt_ids"]),
            "max_tokens": req["max_tokens"], "temperature": 0.0,
            "stream": True}
    rec["sent_t"] = clock()
    try:
        async with session.post(base + "/v1/completions", json=body) as resp:
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}: " \
                               f"{(await resp.text())[:200]}"
                return rec
            async for raw in resp.content:
                line = raw.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[6:]
                if data == b"[DONE]":
                    break
                now = clock()
                event = json.loads(data)
                if "error" in event:
                    rec["error"] = str(event["error"])[:200]
                    continue
                choice = event["choices"][0]
                if choice.get("text"):
                    ids = tokenizer.ids_of(choice["text"])
                    rec["ids"] += ids
                    rec["token_times"] += [now] * len(ids)
                if choice.get("finish_reason"):
                    rec["finish"] = choice["finish_reason"]
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as exc:
        rec["error"] = f"{type(exc).__name__}: {exc}"[:200]
    rec["done_t"] = clock()
    # No EOS in this tokenizer, so a sound request ends at its limit with
    # exactly the tokens asked for, every one of them seen by the client.
    rec["ok"] = (rec["error"] is None and rec["finish"] == "length"
                 and len(rec["ids"]) == req["max_tokens"])
    return rec


def make_session() -> aiohttp.ClientSession:
    return aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=0),
        timeout=aiohttp.ClientTimeout(total=None, sock_read=300),
        trust_env=False)


async def open_loop(base: str, schedule: dict, t_start: float,
                    hooks: list) -> list:
    """Send every request at t_start + due. `hooks` are (offset seconds,
    coroutine function) run at their instants beside the load."""
    records, tasks = [], []
    async with make_session() as session:
        async def run_hook(offset, fn):
            await asyncio.sleep(max(0.0, t_start + offset - clock()))
            return await fn(session)

        hook_tasks = [asyncio.create_task(run_hook(o, fn))
                      for o, fn in hooks]
        for req in sorted(schedule["requests"], key=lambda r: r["due"]):
            due_t = t_start + req["due"]
            delay = due_t - clock()
            if delay > 0:
                await asyncio.sleep(delay)
            rec = new_record(req, due_t)
            records.append(rec)
            tasks.append(asyncio.create_task(send(session, base, req, rec)))
        await asyncio.gather(*tasks)
        await asyncio.gather(*hook_tasks)
    return records


async def closed_loop(base: str, requests: list, clients: int,
                      t_start: float, t_stop: float, window: tuple,
                      hooks: list) -> list:
    """`clients` callers that each wait for their reply; none starts a
    request after t_stop. A request is measured when it was sent inside
    the window (its tokens are credited by arrival time either way)."""
    records = []
    cursor = iter(requests)

    async with make_session() as session:
        async def run_hook(offset, fn):
            await asyncio.sleep(max(0.0, t_start + offset - clock()))
            return await fn(session)

        async def caller():
            while clock() < t_stop:
                req = next(cursor, None)
                if req is None:
                    raise RuntimeError("closed loop ran out of requests: "
                                       "raise list_size in the traffic file")
                now = clock()
                rec = new_record(req, now)
                rec["measured"] = window[0] <= now < window[1]
                records.append(rec)
                await send(session, base, req, rec)

        hook_tasks = [asyncio.create_task(run_hook(o, fn))
                      for o, fn in hooks]
        await asyncio.gather(*[caller() for _ in range(clients)])
        await asyncio.gather(*hook_tasks)
    return records


async def get_text(session, url: str) -> str:
    async with session.get(url) as resp:
        return await resp.text()
