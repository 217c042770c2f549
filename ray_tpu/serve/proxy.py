"""HTTP proxy: per-node ingress routing requests to deployment handles.

Capability parity: reference python/ray/serve/_private/proxy.py (HTTPProxy :699,
ProxyActor :1021) — route-prefix matching, JSON request/response bridging to handles.
aiohttp replaces uvicorn (not baked into this image); the blocking handle call runs on
an executor thread so the event loop keeps accepting connections.
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import json
import threading
import time
from typing import Any, Dict, Optional

import ray_tpu
from ray_tpu.core.exceptions import BackPressureError
from ray_tpu.serve.handle import StreamHandoff
from ray_tpu.util import telemetry

from .controller import CONTROLLER_NAME
from .handle import DeploymentHandle


def _observe_ttft(route: str, seconds: float) -> None:
    """Time-to-first-byte at the ingress: first stream chunk for SSE requests,
    the full response for unary ones — the p50/p99 rows in `ray-tpu status`
    and the SLO input for autoscaling."""
    telemetry.get_histogram(
        "serve_ttft_seconds", "HTTP ingress time-to-first-token/response",
        tag_keys=("route",)).observe(seconds, tags={"route": route})


class ProxyActor:
    def __init__(self, host: str = "127.0.0.1", port: int = 8000):
        self.host = host
        self.port = port
        self._handles: Dict[str, DeploymentHandle] = {}
        self._routes: Dict[str, Dict[str, Any]] = {}
        self._hint_cache = (0.0, None)  # (fetched_at, windowed p50 or None)
        self._ready = threading.Event()
        self._thread = threading.Thread(target=self._serve_forever, daemon=True,
                                        name="serve-http-proxy")
        self._thread.start()

    def ready(self) -> bool:
        self._ready.wait(timeout=30)
        return self._ready.is_set()

    def _retry_after_s(self, fallback: float) -> int:
        """Retry-After for shed responses, derived from the head's WINDOWED
        request-latency history (the recent regime: one service time ~= how
        long until a replica slot frees) — the handle's EWMA is the fallback
        when no history is retained yet. Cached 5s so a shed storm costs one
        state RPC per window, not one per 503."""
        import math

        from .handle import retry_after_from_latency

        now = time.monotonic()
        ts, p50 = self._hint_cache
        if now - ts > 5.0:
            p50 = None
            try:
                from ray_tpu.util.state import serve_latency_hint

                p50 = serve_latency_hint().get("serve_request_p50_s")
            # graftlint: allow[swallowed-exception] no metrics history yet: Retry-After keeps the static fallback
            except Exception:  # noqa: BLE001 — no history/scraper: use fallback
                pass
            self._hint_cache = (now, p50)
        return max(1, int(math.ceil(retry_after_from_latency(p50, fallback))))

    def _shed_response(self, web, e: BackPressureError):
        # the handle's _maybe_shed already counted serve_requests_shed_total;
        # the proxy's job is the wire protocol: 503 + Retry-After
        return web.Response(
            status=503, text=str(e),
            headers={"Retry-After": str(self._retry_after_s(e.retry_after_s))})

    def _refresh_routes(self) -> None:
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        self._routes = ray_tpu.get(controller.get_routing_table.remote())

    def _match(self, path: str):
        best = None
        for prefix, info in self._routes.items():
            if path == prefix or path.startswith(prefix.rstrip("/") + "/") or prefix == "/":
                if best is None or len(prefix) > len(best[0]):
                    best = (prefix, info)
        return best

    def _serve_forever(self) -> None:
        from aiohttp import web

        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)

        async def handler(request: "web.Request") -> "web.Response":
            t0_wall, t0_perf = time.time_ns(), time.perf_counter_ns()
            self._refresh_routes()
            m = self._match(request.path)
            if m is None:
                return web.Response(status=404, text=f"no route for {request.path}")
            prefix, info = m

            # -- request-scoped tracing (W3C traceparent in / out) ---------
            # an incoming traceparent enables tracing for THIS request only:
            # the context set under _in_ctx is itself the enable signal
            # (tracing.is_tracing_enabled honors an active context), so one
            # unauthenticated probe cannot flip a process-wide switch. With
            # tracing globally on, requests without a header root a fresh
            # trace. The proxy span's id becomes the parent the handle->
            # replica->engine chain inherits, so state.request_trace(trace_id)
            # sees one tree spanning proxy and replica processes.
            from ray_tpu.util import tracing

            incoming = tracing.parse_traceparent(
                request.headers.get("traceparent"))
            traced = incoming is not None or tracing.is_tracing_enabled()
            if traced:
                import uuid as _uuid

                trace_id = (incoming["trace_id"] if incoming
                            else _uuid.uuid4().hex)
                upstream_parent = incoming["parent_span_id"] if incoming else ""
                proxy_span_id = tracing.new_span_id()
                child_ctx = {"trace_id": trace_id,
                             "parent_span_id": proxy_span_id}
                traceparent_out = tracing.format_traceparent(
                    trace_id, proxy_span_id)
            else:
                child_ctx = traceparent_out = None

            span_fired = []

            def _finish_span(stream: bool, status: int) -> None:
                # once-only: the streaming path also fires from its finally
                # so a client disconnect mid-stream still records the root
                # span (those aborted requests are the ones worth tracing)
                if not traced or span_fired:
                    return
                span_fired.append(True)
                end_wall_ns = t0_wall + (time.perf_counter_ns() - t0_perf)
                tracing.record_complete_span(
                    "serve.http", t0_wall / 1e9, end_wall_ns / 1e9,
                    trace_id, proxy_span_id, upstream_parent,
                    {"route": prefix, "method": request.method,
                     "path": request.path, "stream": stream,
                     "status": status})

            def _in_ctx(fn):
                """Run fn under the request's trace context and restore the
                (pooled) executor thread afterwards — a leaked contextvar
                would stitch unrelated requests into this trace."""
                if child_ctx is None:
                    return fn

                def wrapped(*a, **kw):
                    token = tracing.set_trace_context(child_ctx)
                    try:
                        return fn(*a, **kw)
                    finally:
                        tracing._ctx.reset(token)
                return wrapped

            def _respond(resp, stream: bool):
                """Close the ingress span and echo the traceparent so callers
                (and tests) learn the trace id to hand request_trace()."""
                if traced:
                    try:
                        resp.headers["traceparent"] = traceparent_out
                    # graftlint: allow[swallowed-exception] response already streaming: headers immutable, trace header is best-effort
                    except Exception:  # noqa: BLE001 — already-prepared stream
                        pass
                    _finish_span(stream, getattr(resp, "status", 200))
                return resp
            key = f"{info['app']}/{info['deployment']}"
            if key not in self._handles:
                self._handles[key] = DeploymentHandle(info["app"], info["deployment"])
            handle = self._handles[key]
            if request.can_read_body:
                try:
                    payload = await request.json()
                except json.JSONDecodeError:
                    payload = (await request.read()).decode()
            else:
                payload = dict(request.query)

            request_dict = {
                "path": request.path[len(prefix.rstrip("/")):] or "/",
                "method": request.method,
                "query": dict(request.query),
                "headers": dict(request.headers),
                "body": payload,
                # when this request came in, on the host's wall clock: an
                # ingress deployment may hand it on (llm: OpenAIRouter ->
                # LLMServer -> engine, which counts the way in from it)
                "arrival_wall_ns": t0_wall,
            }

            # streaming (reference proxy.py:699 ASGI streaming): OpenAI-style
            # {"stream": true} bodies or ?stream=1 run a streaming handle call
            # and forward chunks as they arrive (SSE-compatible)
            # truthiness, matching OpenAIRouter's gate — {"stream": 1} must not
            # desynchronize the proxy (non-stream) from the router (stream)
            wants_stream = (
                (isinstance(payload, dict) and bool(payload.get("stream")))
                or request.query.get("stream") in ("1", "true")
            )
            if wants_stream:
                # handle.remote() blocks on replica discovery (up to 30s) and
                # every next(g) blocks until the replica yields. Each stream
                # gets its OWN single-thread executor: a handful of slow or
                # idle streaming clients must not occupy the event loop's
                # default executor (min(32, cpus+4) threads — ~5 on a small
                # host), which also serves every non-streaming call.
                stream_exec = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="serve-sse")

                def start_stream():
                    return handle.options(method_name="__http__",
                                          stream=True).remote(request_dict)

                _end = object()

                def make_pull(g):
                    def pull():
                        try:
                            return next(g)
                        except StopIteration:
                            return _end
                    return pull

                gen = None
                try:
                    try:
                        gen = await loop.run_in_executor(
                            stream_exec, _in_ctx(start_stream))
                        pull = make_pull(gen)
                        first = await loop.run_in_executor(stream_exec, pull)
                        _observe_ttft(prefix,
                                      (time.perf_counter_ns() - t0_perf) / 1e9)
                        # "stream": true is an OpenAI convention; a deployment
                        # that returned one plain JSON value was not actually
                        # streaming — answer with ordinary JSON instead of a
                        # one-blob SSE body
                        if isinstance(first, (dict, list)):
                            second = await loop.run_in_executor(stream_exec, pull)
                            if second is _end:
                                return _respond(web.json_response(first),
                                                stream=False)
                            pending = [first, second]
                        else:
                            pending = [] if first is _end else [first]
                    except BackPressureError as e:
                        # shed before the stream started: fast 503 + Retry-After
                        return _respond(self._shed_response(web, e),
                                        stream=True)
                    except Exception as e:  # noqa: BLE001 - surface as 500
                        return _respond(web.Response(status=500, text=repr(e)),
                                        stream=True)
                    hdrs = {"Content-Type": "text/event-stream",
                            "Cache-Control": "no-cache"}
                    if traced:  # StreamResponse headers are fixed at prepare()
                        hdrs["traceparent"] = traceparent_out
                    resp = web.StreamResponse(headers=hdrs)
                    await resp.prepare(request)

                    async def write_chunk(chunk):
                        if isinstance(chunk, bytes):
                            await resp.write(chunk)
                        elif isinstance(chunk, str):
                            await resp.write(chunk.encode())
                        else:
                            await resp.write(json.dumps(chunk).encode() + b"\n")

                    handoff = None  # upstream stream adopted from a relay
                    try:
                        for chunk in pending:
                            if isinstance(chunk, StreamHandoff):
                                handoff = chunk.resume()
                                pull = make_pull(handoff)
                            else:
                                await write_chunk(chunk)
                        while True:
                            chunk = await loop.run_in_executor(stream_exec, pull)
                            if chunk is _end:
                                break
                            if isinstance(chunk, StreamHandoff):
                                # a relay deployment (P/D router) handed us its
                                # upstream mid-stream: drain the producing
                                # replica directly, skipping the relay's
                                # per-chunk re-put for the rest of the body
                                handoff = chunk.resume()
                                pull = make_pull(handoff)
                                continue
                            await write_chunk(chunk)
                    except Exception as e:  # noqa: BLE001 — mid-stream: terminate body
                        # client gone or replica error: stop the producer so it
                        # releases engine resources (KV slots) early
                        if gen is not None:
                            stream_exec.submit(gen.close)
                            gen = None
                        if handoff is not None:
                            stream_exec.submit(handoff.close)
                            handoff = None
                        try:
                            await resp.write(f"\nerror: {e!r}\n".encode())
                        # graftlint: allow[swallowed-exception] client socket already closed while reporting a stream error
                        except Exception:  # noqa: BLE001 — socket already closed
                            pass
                    await resp.write_eof()
                    if telemetry.enabled():
                        telemetry.complete(
                            "serve.http", "serve", t0_wall,
                            time.perf_counter_ns() - t0_perf,
                            route=prefix, method=request.method, stream=True,
                            trace_id=trace_id if traced else None)
                    _finish_span(True, 200)
                    return resp
                finally:
                    # covers abrupt exits (client disconnect raising out of
                    # prepare/write, task cancellation): the ingress span is
                    # recorded exactly once either way
                    _finish_span(True, 499)
                    if gen is not None:
                        stream_exec.submit(gen.close)
                    stream_exec.shutdown(wait=False)

            def call():
                return handle.options(method_name="__http__").remote(request_dict).result()

            try:
                result = await loop.run_in_executor(None, _in_ctx(call))
            except BackPressureError as e:
                # admission control tripped: degrade to a FAST rejection the
                # client can back off on, not a queued request that times out
                return _respond(self._shed_response(web, e), stream=False)
            except Exception as e:  # noqa: BLE001 - surface as 500
                return _respond(web.Response(status=500, text=repr(e)),
                                stream=False)
            _observe_ttft(prefix, (time.perf_counter_ns() - t0_perf) / 1e9)
            if telemetry.enabled():
                telemetry.complete(
                    "serve.http", "serve", t0_wall,
                    time.perf_counter_ns() - t0_perf,
                    route=prefix, method=request.method, stream=False,
                    trace_id=trace_id if traced else None)
            from .asgi import RAW_RESPONSE_KEY

            if isinstance(result, dict) and result.get(RAW_RESPONSE_KEY):
                # ASGI deployments return verbatim status/headers/body; repeated
                # header names (multiple Set-Cookie) must survive, so build a
                # multidict rather than a plain dict
                from multidict import CIMultiDict

                hdrs = CIMultiDict()
                for k, v in result["headers"]:
                    if k.lower() != "content-length":
                        hdrs.add(k, v)
                return _respond(web.Response(status=result["status"],
                                             body=result["body"], headers=hdrs),
                                stream=False)
            if isinstance(result, (dict, list)):
                return _respond(web.json_response(result), stream=False)
            if isinstance(result, bytes):
                return _respond(web.Response(body=result), stream=False)
            return _respond(web.Response(text=str(result)), stream=False)

        app = web.Application()
        app.router.add_route("*", "/{tail:.*}", handler)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        ssl_ctx = None
        from ray_tpu.config import CONFIG

        if CONFIG.serve_ingress_tls:
            from ray_tpu.core.tls_utils import ingress_ssl_context

            ssl_ctx = ingress_ssl_context()
        site = web.TCPSite(runner, self.host, self.port, ssl_context=ssl_ctx)
        loop.run_until_complete(site.start())
        self._ready.set()
        loop.run_forever()

    def stop(self) -> None:
        pass
