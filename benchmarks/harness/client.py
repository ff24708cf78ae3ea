#!/usr/bin/env python3
"""The HTTP client of the serving cells, in a process of its own that
never imports JAX. It sends on the schedule whatever the server does,
times every request from the moment it was DUE, and answers its parent
over its stdin and stdout (pipes to the parent, not the run's stdout) with
one JSON line per command:

  {"cmd": "warmup", ...}  one streamed request per listed length, in turn
  {"cmd": "run", ...}     the measured window
  {"cmd": "replay", ...}  non-streamed repeats of some window requests
  {"cmd": "exit"}
"""
from __future__ import annotations

import asyncio
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks.harness import traffic as traffic_mod  # noqa: E402


async def _post(host: str, port: int, body: Dict[str, Any], *,
                timeout_s: float, clock0: float) -> Dict[str, Any]:
    """One /v1/completions call over a connection of its own. Returns
    the status, the generated token ids and, for a stream, the arrival
    time of every token (seconds on this process's clock, from clock0)."""
    rec: Dict[str, Any] = {"status": None, "tokens": [], "token_t": [],
                           "done": False, "error": None}
    writer = None
    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(host, port), timeout_s)
        payload = json.dumps(body).encode()
        writer.write(
            b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\nConnection: close\r\n"
            b"Content-Length: " + str(len(payload)).encode()
            + b"\r\n\r\n" + payload)
        await writer.drain()
        rec["sent_t"] = time.perf_counter() - clock0
        status_line = await asyncio.wait_for(reader.readline(), timeout_s)
        rec["status"] = int(status_line.split()[1])
        if rec["status"] != 200 or not body.get("stream"):
            raw = await asyncio.wait_for(reader.read(), timeout_s)
            rec["end_t"] = time.perf_counter() - clock0
            _head, _, tail = raw.partition(b"\r\n\r\n")
            if rec["status"] == 200:
                # a chunked or plain body: the JSON object is its only
                # brace-balanced part
                text = tail[tail.index(b"{"):tail.rindex(b"}") + 1]
                out = json.loads(text)["choices"][0]["text"]
                rec["tokens"] = [int(t) for t in out.split()]
                rec["done"] = True
            else:
                rec["error"] = tail.decode(errors="replace")[:200]
            return rec
        while True:
            line = await asyncio.wait_for(reader.readline(), timeout_s)
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            now = time.perf_counter() - clock0
            data = line[6:].strip()
            if data == b"[DONE]":
                rec["done"] = True
                break
            obj = json.loads(data)
            if "choices" not in obj:
                rec["error"] = str(obj)[:200]
                continue
            for tok in obj["choices"][0].get("text", "").split():
                rec["tokens"].append(int(tok))
                rec["token_t"].append(now)
        rec["end_t"] = time.perf_counter() - clock0
    except (OSError, asyncio.TimeoutError, ValueError, IndexError,
            KeyError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["end_t"] = time.perf_counter() - clock0
    finally:
        if writer is not None:
            writer.close()
    return rec


def _body(cmd: Dict[str, Any], req: Dict[str, Any], stream: bool
          ) -> Dict[str, Any]:
    return {"model": cmd["model"], "stream": stream,
            "max_tokens": int(req["max_tokens"]),
            "prompt": traffic_mod.prompt_tokens(
                cmd["seed"], req["i"], req["prompt_len"], cmd["vocab"])}


async def _warmup(cmd: Dict[str, Any]) -> Dict[str, Any]:
    out = []
    clock0 = time.perf_counter()
    for k, length in enumerate(cmd["lengths"]):
        req = {"i": 10_000_000 + k, "prompt_len": length,
               "max_tokens": cmd["max_tokens"]}
        rec = await _post(cmd["host"], cmd["port"], _body(cmd, req, True),
                          timeout_s=cmd["timeout_s"], clock0=clock0)
        out.append({"prompt_len": length, "status": rec["status"],
                    "n": len(rec["tokens"]), "done": rec["done"],
                    "error": rec["error"],
                    "seconds": rec.get("end_t")})
    return {"ok": all(r["status"] == 200 and r["done"] for r in out),
            "records": out}


async def _run(cmd: Dict[str, Any], state: Dict[str, Any]
               ) -> Dict[str, Any]:
    traffic = cmd["traffic"]
    seconds = float(cmd["seconds"])
    plan = traffic_mod.plan(traffic, cmd["seed"], seconds)
    reqs = plan["requests"]
    # bodies are built before the clock starts: generating tokens is the
    # generator's work, not the server's
    bodies = [_body(cmd, r, True) for r in reqs]
    records: List[Optional[Dict[str, Any]]] = [None] * len(reqs)
    clock0 = time.perf_counter()
    wall0 = time.time()

    async def one(i: int, due: float) -> None:
        start = time.perf_counter() - clock0
        rec = await _post(cmd["host"], cmd["port"], bodies[i],
                          timeout_s=cmd["timeout_s"], clock0=clock0)
        rec.update(i=i, due_t=due, start_t=start,
                   prompt_len=reqs[i]["prompt_len"],
                   max_tokens=reqs[i]["max_tokens"])
        records[i] = rec

    if traffic["loop"] == "open":
        tasks = []
        for i, r in enumerate(reqs):
            delay = r["due_s"] - (time.perf_counter() - clock0)
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(one(i, r["due_s"])))
        done, pending = await asyncio.wait(
            tasks, timeout=max(0.0, seconds + float(cmd["drain_s"])
                               - (time.perf_counter() - clock0)))
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    else:
        nxt = iter(range(len(reqs)))

        async def worker() -> None:
            for i in nxt:
                now = time.perf_counter() - clock0
                if now >= seconds:
                    return
                await one(i, now)

        workers = [asyncio.ensure_future(worker())
                   for _ in range(int(traffic["clients"]))]
        done, pending = await asyncio.wait(
            workers, timeout=seconds + float(cmd["drain_s"]))
        for t in pending:
            t.cancel()
        await asyncio.gather(*workers, return_exceptions=True)
    end = time.perf_counter() - clock0
    sent = [r for r in records if r is not None]
    state["bodies"] = bodies
    state["records"] = records
    late = [r["start_t"] - r["due_t"] for r in sent]
    return {"ok": True, "wall0": wall0, "elapsed_s": end,
            "planned": len(reqs), "records": sent,
            "lateness_s": {"max": max(late, default=0.0),
                           "mean": sum(late) / max(1, len(late))}}


async def _replay(cmd: Dict[str, Any], state: Dict[str, Any]
                  ) -> Dict[str, Any]:
    out = []
    clock0 = time.perf_counter()
    for i in cmd["indices"]:
        body = dict(state["bodies"][i], stream=False)
        rec = await _post(cmd["host"], cmd["port"], body,
                          timeout_s=cmd["timeout_s"], clock0=clock0)
        out.append({"i": i, "status": rec["status"],
                    "tokens": rec["tokens"], "error": rec["error"]})
    return {"ok": True, "records": out}


def main() -> int:
    state: Dict[str, Any] = {}
    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        cmd = json.loads(line)
        if cmd["cmd"] == "exit":
            break
        try:
            if cmd["cmd"] == "warmup":
                reply = loop.run_until_complete(_warmup(cmd))
            elif cmd["cmd"] == "run":
                reply = loop.run_until_complete(_run(cmd, state))
            elif cmd["cmd"] == "replay":
                reply = loop.run_until_complete(_replay(cmd, state))
            else:
                reply = {"ok": False, "error": f"unknown {cmd['cmd']!r}"}
        except Exception as e:  # noqa: BLE001 - reported to the parent
            reply = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        sys.stdout.write(json.dumps(reply) + "\n")
        sys.stdout.flush()
    loop.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
