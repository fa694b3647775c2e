"""Async HTTP/SSE serving frontend — stdlib asyncio only, no new deps.

A deliberately small HTTP/1.1 surface over ``Engine.generate``:

  POST /v1/generate     body: {"prompt": [int token ids], "max_new_tokens",
                        "temperature", "priority", "prefix_len",
                        "deadline_s"} → ``text/event-stream`` of one SSE
                        event per token (``data: {"token": t}``), terminated
                        by ``data: {"done": true, "stop_reason": ...}``.
                        While the engine is overloaded (admission control
                        above ``ResilienceConfig.queue_high_water``) the
                        request is rejected up front with 429 + a jittered
                        exponential ``Retry-After``; a draining engine
                        answers 503.
  GET  /v1/metrics      JSON: throughput + SLA report (TTFT/TPOT
                        percentiles per priority class, preemption and
                        prefix-hit rates, queue depth, pool occupancy).
  GET  /healthz         JSON health snapshot (engine state ok | degraded |
                        draining, queue depth, active slots, pool
                        occupancy, watchdog/error counters).  200 while
                        ``ok`` or merely ``degraded`` (the engine is still
                        serving), 503 + Retry-After when draining.
  GET  /health          200 ok (legacy liveness probe; /healthz is the
                        informative one).

Error bodies are structured JSON — ``{"error": {"type", "reason"}}`` —
distinguishing client mistakes (400: the reason names the offending field)
from server faults (500: the reason is generic, the traceback goes to the
``repro.serve.http`` logger, never to the client).

Streams emit an SSE comment heartbeat (``: hb``) every
``ResilienceConfig.heartbeat_s`` while the engine is between tokens, so
proxies and clients can tell a slow generation from a dead connection.
Client disconnect mid-stream is detected on the next write; the
generator's cleanup path cancels the request, which releases its pages and
resets its slot (including the speculative draft-cache row) immediately.
"""

from __future__ import annotations

import asyncio
import json
import logging
import math

from repro.serve import resilience as rsl
from repro.serve.config import SamplingParams

_MAX_BODY = 1 << 20
log = logging.getLogger("repro.serve.http")


class _BadRequest(ValueError):
    """Client error: ``reason`` becomes the 400 body's error.reason."""


def _error_body(type_: str, reason: str) -> bytes:
    return json.dumps({"error": {"type": type_, "reason": reason}}).encode()


def _http(status: str, ctype: str, body: bytes, *, stream: bool = False,
          extra: dict | None = None):
    head = f"HTTP/1.1 {status}\r\nContent-Type: {ctype}\r\n"
    for k, v in (extra or {}).items():
        head += f"{k}: {v}\r\n"
    head += ("Cache-Control: no-store\r\nConnection: close\r\n\r\n" if stream
             else f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
    return head.encode() + body


async def _read_request(reader: asyncio.StreamReader):
    """Parse request line + headers + (Content-Length) body; None on EOF
    or malformed input."""
    try:
        head = await reader.readuntil(b"\r\n\r\n")
    except (asyncio.IncompleteReadError, asyncio.LimitOverrunError):
        return None
    lines = head.decode("latin-1").split("\r\n")
    parts = lines[0].split(" ")
    if len(parts) < 2:
        return None
    method, path = parts[0].upper(), parts[1]
    headers = {}
    for ln in lines[1:]:
        if ":" in ln:
            k, v = ln.split(":", 1)
            headers[k.strip().lower()] = v.strip()
    body = b""
    n = int(headers.get("content-length", 0) or 0)
    if n:
        if n > _MAX_BODY:
            return None
        body = await reader.readexactly(n)
    return method, path, headers, body


def _parse_generate(body: bytes) -> dict:
    """Validate the /v1/generate body; raises _BadRequest naming the field."""
    try:
        spec = json.loads(body or b"{}")
    except ValueError:
        raise _BadRequest("body: not valid JSON")
    if not isinstance(spec, dict):
        raise _BadRequest("body: expected a JSON object")
    if "prompt" not in spec:
        raise _BadRequest("prompt: missing (non-empty token id list)")
    try:
        prompt = [int(t) for t in spec["prompt"]]
    except (TypeError, ValueError):
        raise _BadRequest("prompt: expected a list of integer token ids")
    if not prompt:
        raise _BadRequest("prompt: non-empty token id list")
    spec["prompt"] = prompt
    for key, cast in (("max_new_tokens", int), ("temperature", float),
                      ("priority", int), ("deadline_s", float)):
        if spec.get(key) is not None:
            try:
                spec[key] = cast(spec[key])
            except (TypeError, ValueError):
                raise _BadRequest(f"{key}: expected {cast.__name__}")
    return spec


class Server:
    """One engine behind one listening socket, all requests batched through
    the engine's shared driver task."""

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 8080):
        self.engine = engine
        self.host = host
        self.port = port
        self._server: asyncio.base_events.Server | None = None
        self._uid = 1 << 32   # below the engine's auto-uid range
        res = engine.resilience
        self.heartbeat_s = res.heartbeat_s
        # one shared backoff: while the engine stays overloaded, consecutive
        # rejections advance the attempt counter so the advertised
        # Retry-After values spread retrying clients out; the first accepted
        # request resets it
        self._backoff = rsl.Backoff(res.retry_after_base_s,
                                    res.retry_after_cap_s, seed=0)
        self._reject_streak = 0

    async def start(self):
        self._server = await asyncio.start_server(self._handle, self.host,
                                                  self.port)
        return self._server.sockets[0].getsockname()[1]   # resolved port

    async def stop(self):
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()

    async def serve_forever(self):
        await self.start()
        async with self._server:
            await self._server.serve_forever()

    def _retry_after(self) -> dict:
        delay = self._backoff.delay(self._reject_streak)
        self._reject_streak += 1
        return {"Retry-After": str(max(1, math.ceil(delay)))}

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter):
        try:
            req = await _read_request(reader)
            if req is None:
                writer.write(_http(
                    "400 Bad Request", "application/json",
                    _error_body("bad_request", "malformed HTTP request")))
            else:
                method, path, _, body = req
                try:
                    await self._route(writer, method, path, body)
                except (ConnectionResetError, BrokenPipeError,
                        asyncio.CancelledError):
                    raise
                except Exception:
                    # server fault: full traceback to the log, a generic
                    # body to the client (internals never leak over HTTP)
                    log.exception("unhandled error serving %s %s",
                                  method, path)
                    writer.write(_http(
                        "500 Internal Server Error", "application/json",
                        _error_body("server_error", "internal error; see "
                                    "server log")))
            await writer.drain()
        except (ConnectionResetError, BrokenPipeError, asyncio.CancelledError):
            pass
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

    async def _route(self, writer, method: str, path: str, body: bytes):
        if method == "POST" and path == "/v1/generate":
            await self._generate(writer, body)
        elif method == "GET" and path == "/v1/metrics":
            payload = json.dumps(self._metrics()).encode()
            writer.write(_http("200 OK", "application/json", payload))
        elif method == "GET" and path == "/healthz":
            snap = self.engine.healthz()
            payload = json.dumps(snap).encode()
            if snap["state"] == "draining":
                writer.write(_http("503 Service Unavailable",
                                   "application/json", payload,
                                   extra=self._retry_after()))
            else:
                writer.write(_http("200 OK", "application/json", payload))
        elif method == "GET" and path == "/health":
            writer.write(_http("200 OK", "text/plain", b"ok"))
        else:
            writer.write(_http("404 Not Found", "application/json",
                               _error_body("not_found", path)))

    def _metrics(self) -> dict:
        eng = self.engine
        return {"throughput": eng.throughput(), "sla": eng.sla_report(),
                "health": eng.healthz(),
                "active": sum(1 for s in eng.slots if s.req is not None),
                "queued": len(eng.queue),
                "ticks": eng.tracer.summary(1000)}

    async def _generate(self, writer: asyncio.StreamWriter, body: bytes):
        try:
            spec = _parse_generate(body)
        except _BadRequest as exc:
            writer.write(_http("400 Bad Request", "application/json",
                               _error_body("bad_request", str(exc))))
            return
        if self.engine.health.state == "draining":
            writer.write(_http(
                "503 Service Unavailable", "application/json",
                _error_body("draining", "engine is draining; retry against "
                            "another replica"), extra=self._retry_after()))
            return
        if self.engine.overloaded():
            writer.write(_http(
                "429 Too Many Requests", "application/json",
                _error_body("overloaded", "queue above high-water mark; "
                            "honor Retry-After"), extra=self._retry_after()))
            return
        self._reject_streak = 0
        sampling = SamplingParams(
            max_new_tokens=int(spec.get("max_new_tokens", 32)),
            temperature=float(spec.get("temperature", 0.0)))
        self._uid += 1
        uid = self._uid
        stream = self.engine.generate(
            spec["prompt"], sampling, priority=int(spec.get("priority", 0)),
            prefix_len=spec.get("prefix_len"), uid=uid,
            deadline_s=spec.get("deadline_s"))
        writer.write(_http("200 OK", "text/event-stream", b"", stream=True))
        await writer.drain()
        pending: asyncio.Future | None = None
        try:
            it = stream.__aiter__()
            while True:
                if pending is None:
                    # NOT wait_for: cancelling __anext__ on a heartbeat
                    # timeout would kill the generator (and the request);
                    # the same future is re-awaited across heartbeats
                    pending = asyncio.ensure_future(it.__anext__())
                done_set, _ = await asyncio.wait({pending},
                                                 timeout=self.heartbeat_s)
                if not done_set:
                    writer.write(b": hb\n\n")   # SSE comment: liveness only
                    await writer.drain()
                    continue
                try:
                    tok = pending.result()
                except StopAsyncIteration:
                    pending = None
                    break
                pending = None
                writer.write(f"data: {json.dumps({'token': tok})}\n\n"
                             .encode())
                # drain per token: a disconnected client raises here, and
                # the stream's finally-cancel frees the pages right away
                await writer.drain()
        finally:
            if pending is not None:
                pending.cancel()
            await stream.aclose()
            req = next((r for r in reversed(self.engine.finished)
                        if r.uid == uid), None)
            done = {"done": True,
                    "stop_reason": getattr(req, "stop_reason", None)}
            path = getattr(req, "degrade_path", None)
            if path:
                done["degraded"] = list(path)
            try:
                writer.write(f"data: {json.dumps(done)}\n\n".encode())
                await writer.drain()
            except (ConnectionResetError, BrokenPipeError):
                pass


async def run_server(engine, host: str = "127.0.0.1", port: int = 8080):
    srv = Server(engine, host, port)
    await srv.serve_forever()
