"""LLMServer Serve deployment + OpenAI-compatible router.

Capability parity: reference python/ray/llm/_internal/serve/deployments/llm/
llm_server.py:409 (``LLMServer`` — Serve deployment wrapping an engine, OpenAI
chat/completions) and serve/routers/ + builders/ (``build_openai_app`` multi-model
ingress). The engine here is ``JaxLLMEngine`` (TP over the replica's device mesh)
instead of vLLM.
"""
from __future__ import annotations

import dataclasses
import logging
import time
import uuid
from typing import Any, Dict, List, Optional

from .config import LLMConfig, SamplingParams
from .engine import JaxLLMEngine

_LOGGER = logging.getLogger(__name__)


def _sampling_from_body(body: Dict[str, Any]) -> SamplingParams:
    return SamplingParams(
        max_tokens=int(body.get("max_tokens", 64)),
        temperature=float(body.get("temperature", 1.0)),
        top_p=float(body.get("top_p", 1.0)),
        top_k=int(body.get("top_k", 0)),
        seed=body.get("seed"),
    )


def render_chat_template(messages: List[Dict[str, str]]) -> str:
    """Minimal chat template (reference: HF chat templates via vLLM's tokenizer)."""
    parts = [f"{m.get('role', 'user')}: {m.get('content', '')}" for m in messages]
    return "\n".join(parts) + "\nassistant:"


def _usage(prompt_tokens: int, completion_tokens: int) -> Dict[str, int]:
    return {
        "prompt_tokens": prompt_tokens,
        "completion_tokens": completion_tokens,
        "total_tokens": prompt_tokens + completion_tokens,
    }


def _chat_envelope(model: str, text: str, finish_reason, usage) -> Dict[str, Any]:
    return {
        "id": f"chatcmpl-{uuid.uuid4().hex[:24]}",
        "object": "chat.completion",
        "created": int(time.time()),
        "model": model,
        "choices": [{
            "index": 0,
            "message": {"role": "assistant", "content": text},
            "finish_reason": finish_reason,
        }],
        "usage": usage,
    }


def _completion_envelope(model: str, text: str, finish_reason, usage) -> Dict[str, Any]:
    return {
        "id": f"cmpl-{uuid.uuid4().hex[:24]}",
        "object": "text_completion",
        "created": int(time.time()),
        "model": model,
        "choices": [{"index": 0, "text": text, "finish_reason": finish_reason}],
        "usage": usage,
    }


def _models_list(model_ids) -> Dict[str, Any]:
    return {
        "object": "list",
        "data": [{"id": m, "object": "model", "owned_by": "ray_tpu"}
                 for m in sorted(model_ids)],
    }


class LLMServer:
    """Serve deployment hosting one model's engine.

    Deploy via ``build_openai_app`` or directly:
        app = serve.deployment(LLMServer).bind(llm_config)
    """

    def __init__(self, llm_config: LLMConfig, engine: Optional[JaxLLMEngine] = None,
                 prefill_handle=None):
        self.llm_config = llm_config
        self.engine = engine or JaxLLMEngine(llm_config)
        # decode-pool replicas get a handle to the prefill pool so a
        # device-plane failure mid-stream can re-prefill over the host path
        # WITHOUT unwinding through the router (build_pd_openai_app wires it)
        self.prefill_handle = prefill_handle
        self.engine.start()

    # -- OpenAI endpoints --------------------------------------------------------
    # arrival_wall_ns: the HTTP proxy's time.time_ns() on taking the request
    # in, handed on by OpenAIRouter; the engine counts the way in from it
    # (metrics(): ingress_ns_total). A handle call has none.
    def chat(self, body: Dict[str, Any], arrival_wall_ns: Optional[int] = None):
        prompt = render_chat_template(body.get("messages", []))
        if body.get("stream"):
            return self._sse_stream(prompt, body, True, arrival_wall_ns)
        out = self.engine.generate_sync(prompt, _sampling_from_body(body),
                                        arrival_wall_ns=arrival_wall_ns)
        return _chat_envelope(
            body.get("model", self.llm_config.model_id), out.text, out.finish_reason,
            _usage(out.num_prompt_tokens, out.num_generated_tokens))

    def completions(self, body: Dict[str, Any], arrival_wall_ns: Optional[int] = None):
        if body.get("stream"):
            return self._sse_stream(body.get("prompt", ""), body, False, arrival_wall_ns)
        out = self.engine.generate_sync(body.get("prompt", ""), _sampling_from_body(body),
                                        arrival_wall_ns=arrival_wall_ns)
        return _completion_envelope(
            body.get("model", self.llm_config.model_id), out.text, out.finish_reason,
            _usage(out.num_prompt_tokens, out.num_generated_tokens))

    def _sse_stream(self, prompt: str, body: Dict[str, Any], chat: bool,
                    arrival_wall_ns: Optional[int] = None):
        """OpenAI ``stream: true``: yield SSE frames ("data: {chunk}\\n\\n" ...
        "data: [DONE]\\n\\n") as the engine produces tokens. Runs as a streaming
        actor method through Serve (reference proxy.py:699 ASGI streaming)."""
        return self._sse_frames(
            lambda rid: self.engine.generate(
                prompt, _sampling_from_body(body), request_id=rid,
                arrival_wall_ns=arrival_wall_ns),
            body, chat)

    def decode_stream(self, prefill_result, body: Dict[str, Any],
                      chat: bool):
        """Streaming decode side of P/D disaggregation: continue from a
        transferred prefill and yield SSE frames (reference
        prefill_decode_disagg + ASGI streaming).

        Failure handling lives HERE, not in the router: the router hands this
        stream straight to the HTTP proxy (StreamHandoff) before the first
        frame, so nobody upstream can splice in a replacement. A device-plane
        failure — the prefill result itself, or the KV pull failing mid-page
        -stream — re-prefills over the host path through ``prefill_handle``
        and resumes the SAME SSE stream: tokens the first attempt already
        yielded are skipped by count, which replays exactly under
        deterministic decoding (greedy or seeded), the caveat the router's
        unary fallback shares."""
        sampling = _sampling_from_body(body)
        pre_err: Optional[BaseException] = None
        pre: Optional[Dict[str, Any]] = None
        try:
            pre = _materialize_prefill(prefill_result)
        except Exception as e:
            if self.prefill_handle is None or not _is_device_plane_error(e):
                raise
            pre_err = e

        def _host_re_prefill():
            if pre is not None:
                _release_orphan_export(pre)
            prompt = (render_chat_template(body.get("messages", []))
                      if chat else body.get("prompt", ""))
            fb_body = dict(body)
            fb_body["_kv_host_fallback"] = True
            return self.prefill_handle.options(method_name="prefill").remote(
                prompt, fb_body).result()

        def start_gen(rid):
            yielded = 0
            try:
                if pre_err is not None:
                    raise pre_err
                for out in self.engine.generate_from_prefill(
                        pre, sampling, request_id=rid):
                    yielded += len(out.token_ids)
                    yield out
                return
            except GeneratorExit:
                raise
            except Exception as e:
                if self.prefill_handle is None or not _is_device_plane_error(e):
                    raise
                _LOGGER.warning(
                    "device-plane KV handoff failed mid-stream for key %s "
                    "(%r); resuming over the host path",
                    (pre or {}).get("kv_key"), e)
            pre_fb = _host_re_prefill()
            skip = yielded
            fb_rid = uuid.uuid4().hex
            try:
                for out in self.engine.generate_from_prefill(
                        pre_fb, sampling, request_id=fb_rid):
                    ids = out.token_ids
                    if skip:
                        k = min(skip, len(ids))
                        skip -= k
                        ids = ids[k:]
                        if not ids and not out.finish_reason:
                            continue
                        out = dataclasses.replace(out, token_ids=ids)
                    yield out
            except GeneratorExit:
                self.engine.abort(fb_rid)
                raise

        return self._sse_frames(
            start_gen, body, chat,
            presynth=(pre or {}).get("first_text") or "")

    def _sse_frames(self, start_gen, body: Dict[str, Any], chat: bool,
                    presynth: str = ""):
        import json as _json

        model = body.get("model", self.llm_config.model_id)
        rid = (f"chatcmpl-{uuid.uuid4().hex[:24]}" if chat
               else f"cmpl-{uuid.uuid4().hex[:24]}")
        created = int(time.time())

        def frame(payload: Dict[str, Any]) -> str:
            return f"data: {_json.dumps(payload)}\n\n"

        def choices(delta_or_text, finish_reason):
            if chat:
                return [{"index": 0, "delta": delta_or_text,
                         "finish_reason": finish_reason}]
            return [{"index": 0, "text": delta_or_text,
                     "finish_reason": finish_reason}]

        obj = "chat.completion.chunk" if chat else "text_completion"

        tokenizer = self.engine.tokenizer

        def gen():
            if chat:
                yield frame({"id": rid, "object": obj, "created": created,
                             "model": model,
                             "choices": choices({"role": "assistant"}, None)})
            finish = None
            # deltas come from re-decoding the FULL id sequence: per-chunk
            # decode drops BPE leading-space markers and splits multi-byte
            # UTF-8, diverging from the non-streaming response text
            all_ids: List[int] = []
            emitted = ""

            def delta_frame(delta_text):
                delta = {"content": delta_text} if chat else delta_text
                return frame({"id": rid, "object": obj, "created": created,
                              "model": model, "choices": choices(delta, None)})

            if presynth:
                # P/D: prefill already sampled AND rendered the first token
                # (prefill_only's ``first_text``), so emit it before engine
                # admission — the first content frame doesn't wait for the KV
                # pull to start. The engine replays the same token id, whose
                # re-decode lands inside ``emitted`` and yields no frame.
                yield delta_frame(presynth)
                emitted = presynth
            eng_rid = uuid.uuid4().hex
            try:
                for out in start_gen(eng_rid):
                    finish = out.finish_reason
                    all_ids.extend(out.token_ids)
                    full = tokenizer.decode(all_ids)
                    if full.endswith("�"):
                        continue  # mid-codepoint: wait for the next chunk
                    delta_text = full[len(emitted):]
                    emitted = full
                    if delta_text:
                        yield delta_frame(delta_text)
            except GeneratorExit:
                # consumer abandoned the stream (client disconnect): stop the
                # engine request so its KV slot/blocks free now, not at max_tokens
                self.engine.abort(eng_rid)
                raise
            # flush a tail withheld by the mid-codepoint guard (generation can
            # legitimately stop mid-sequence at max_tokens): match generate_sync
            tail = tokenizer.decode(all_ids)[len(emitted):]
            if tail:
                yield delta_frame(tail)
            yield frame({"id": rid, "object": obj, "created": created,
                         "model": model,
                         "choices": choices({} if chat else "", finish or "stop")})
            yield "data: [DONE]\n\n"

        return gen()

    # -- P/D disaggregation endpoints (reference prefill_decode_disagg/) ---------
    def prefill(self, prompt: str, body: Dict[str, Any]) -> Dict[str, Any]:
        return self.engine.prefill_only(
            prompt, _sampling_from_body(body),
            force_host=bool(body.get("_kv_host_fallback")))

    def release_prefill(self, kv_key: str) -> None:
        """Ack from the router after decode pulled the device-resident KV."""
        self.engine.release_prefill_export(kv_key)

    def decode_from_prefill(self, prefill_result,
                            body: Dict[str, Any]) -> Dict[str, Any]:
        prefill_result = _materialize_prefill(prefill_result)
        params = _sampling_from_body(body)
        ids: List[int] = []
        last = None
        for chunk in self.engine.generate_from_prefill(prefill_result, params):
            ids.extend(chunk.token_ids)
            last = chunk
        return {
            "text": self.engine.tokenizer.decode(ids),
            "token_ids": ids,
            "finish_reason": last.finish_reason,
            "num_prompt_tokens": len(prefill_result["prompt_ids"]),
            "num_generated_tokens": len(ids),
        }

    def model_id(self) -> str:
        return self.llm_config.model_id

    def metrics(self) -> Dict[str, Any]:
        return self.engine.metrics()

    def device_report(self) -> Dict[str, Any]:
        return self.engine.device_report()

    # scheduler-loop stall bound for check_health: generous enough for a cold
    # XLA compile of a big model's burst program, far below a wedged device
    ENGINE_STALL_S = 300.0

    def check_health(self) -> None:
        if self.engine._shutdown:
            raise RuntimeError("engine stopped")
        import time as _time

        eng = self.engine
        # a live loop ticks every burst; requests in flight with a stale tick
        # means the scheduler thread is wedged (device hang, deadlock) — fail
        # health so the serve controller replaces this replica
        if eng._loop_thread is not None and (eng.num_active or eng.num_pending):
            stale = _time.monotonic() - eng._last_tick_monotonic
            if stale > self.ENGINE_STALL_S:
                raise RuntimeError(
                    f"engine scheduler loop stalled for {stale:.0f}s with "
                    f"{eng.num_active} active / {eng.num_pending} pending "
                    "requests")

    def shutdown(self) -> None:
        self.engine.shutdown()


class OpenAIRouter:
    """Multi-model ingress: routes /v1/* to per-model LLMServer deployments."""

    def __init__(self, **model_handles):
        # model_id -> DeploymentHandle to an LLMServer deployment
        self.handles = model_handles

    def _pick(self, model: Optional[str]):
        if model in self.handles:
            return self.handles[model]
        if model is None and len(self.handles) == 1:
            return next(iter(self.handles.values()))
        raise ValueError(f"unknown model {model!r}; served: {sorted(self.handles)}")

    def handle_http(self, request: Dict[str, Any]):
        path, body = request["path"], request.get("body") or {}
        if path.endswith("/models"):
            return _models_list(self.handles)
        model = body.get("model") if isinstance(body, dict) else None
        handle = self._pick(model)
        stream = bool(isinstance(body, dict) and body.get("stream"))
        if path.endswith("/chat/completions"):
            h = handle.options(method_name="chat", stream=stream)
        elif path.endswith("/completions"):
            h = handle.options(method_name="completions", stream=stream)
        else:
            raise ValueError(f"unsupported path {path!r}")
        # the proxy's arrival stamp travels on with the request
        arrival = request.get("arrival_wall_ns")
        resp = h.remote(body) if arrival is None else h.remote(
            body, arrival_wall_ns=arrival)
        # streaming: return the response generator itself — the router is called
        # with a streaming method too, so each SSE frame re-streams through it
        return resp if stream else resp.result()

    # direct-handle convenience (tests, in-cluster clients)
    def chat(self, body: Dict[str, Any]) -> Dict[str, Any]:
        return self.handle_http({"path": "/v1/chat/completions", "method": "POST", "body": body})

    def completions(self, body: Dict[str, Any]) -> Dict[str, Any]:
        return self.handle_http({"path": "/v1/completions", "method": "POST", "body": body})


def _materialize_prefill(pre):
    """Resolve an overlapped prefill handoff on the decode side.

    The PDRouter forwards the prefill pool's response FUTURE straight into the
    decode call, so decode dispatch/scheduling overlaps prefill execution
    instead of waiting for the router to materialize the result first — one
    control round trip off the TTFT critical path. A prefill failure re-raises
    here and surfaces through the decode call's error path."""
    return pre.result() if hasattr(pre, "result") else pre


def _release_orphan_export(pre: Dict[str, Any]) -> None:
    """Free an orphaned prefill KV export now instead of waiting for its TTL.
    Dials the exporting process's arm channel directly off the handle —
    pool-safe: a ``release_prefill`` deployment call would p2c-route to an
    arbitrary pool replica, not the one that exported."""
    handle = pre.get("kv_handle")
    if handle is None:
        return
    try:
        from ray_tpu.core.device_plane import release_remote

        release_remote(handle)
    except Exception as rel_err:
        _LOGGER.warning(
            "could not release prefill KV export %s after host "
            "fallback (%r); the prefill engine pins it until the "
            "TTL backstop", pre.get("kv_key"), rel_err)


def _is_device_plane_error(e: BaseException) -> bool:
    """Match a DevicePlaneError surfaced through the actor-RPC boundary (the
    original may arrive re-raised, wrapped, or as a cause)."""
    seen = set()
    cur: Optional[BaseException] = e
    while cur is not None and id(cur) not in seen:
        seen.add(id(cur))
        if type(cur).__name__ == "DevicePlaneError":
            return True
        cur = cur.__cause__ or cur.__context__
    return "DevicePlaneError" in str(e)


class PDRouter:
    """Prefill/decode-disaggregated ingress: prompts prefill on one replica pool,
    the KV crosses to a decode pool that streams the completion (reference
    python/ray/llm/_internal/serve/deployments/prefill_decode_disagg/). The KV hop
    is device-to-device over the transfer plane (core/device_plane.py — DCN on
    pods) when available; only a ~1 KB handle rides the control message. Host
    arrays through the object store are the fallback."""

    def __init__(self, prefill_handle, decode_handle, model_id: str):
        self.prefill_handle = prefill_handle
        self.decode_handle = decode_handle
        self.model_id = model_id

    def _release_orphan(self, pre: Dict[str, Any]) -> None:
        _release_orphan_export(pre)

    def _settle_prefill(self, pre_resp, timeout_s: float = 5.0):
        """Materialize an overlapped prefill response for fallback handling.
        Returns the prefill dict, or None when the result is unobtainable
        (the producer died taking its result object with it) — the fallback
        path proceeds either way; only the early orphan release is skipped."""
        try:
            return pre_resp.result(timeout_s=timeout_s)
        # graftlint: allow[swallowed-exception] producer gone with its result: the export TTL backstop reaps it
        except Exception:
            return None

    def _run(self, prompt: str, body: Dict[str, Any]) -> Dict[str, Any]:
        # the decode call is dispatched IMMEDIATELY with the prefill pool's
        # response future: the decode replica resolves it itself
        # (_materialize_prefill), so decode dispatch/scheduling overlaps
        # prefill execution instead of serializing behind a router-side
        # result() round trip.
        pre_resp = self.prefill_handle.options(method_name="prefill").remote(
            prompt, body)
        # KV release: the decode replica acks the prefill side's device-plane
        # export right after its pull (fetch(..., release=True)); no router hop.
        try:
            return self.decode_handle.options(
                method_name="decode_from_prefill").remote(
                    pre_resp, body).result()
        except Exception as e:
            if not _is_device_plane_error(e):
                # a prefill failure is the request's real fate: surface it
                # (with the handle's replica-retry plane) instead of the
                # decode-side wrapper it arrived in
                pre_resp.result()
                raise
            # Device pull failed (topology mismatch, prefill replica restarted
            # or died mid-transfer): redo the request on the host path — the
            # old always-works behavior.
            pre = self._settle_prefill(pre_resp)
            if pre is not None and "kv_handle" not in pre:
                raise
            _LOGGER.warning(
                "device-plane KV handoff failed for key %s (%r); retrying "
                "over the host path", (pre or {}).get("kv_key"), e)
            if pre is not None:
                self._release_orphan(pre)
            body = dict(body)
            body["_kv_host_fallback"] = True
            pre = self.prefill_handle.options(method_name="prefill").remote(
                prompt, body).result()
            return self.decode_handle.options(
                method_name="decode_from_prefill").remote(pre, body).result()

    def chat(self, body: Dict[str, Any]) -> Dict[str, Any]:
        out = self._run(render_chat_template(body.get("messages", [])), body)
        return _chat_envelope(
            body.get("model", self.model_id), out["text"], out["finish_reason"],
            _usage(out["num_prompt_tokens"], out["num_generated_tokens"]))

    def completions(self, body: Dict[str, Any]) -> Dict[str, Any]:
        out = self._run(body.get("prompt", ""), body)
        return _completion_envelope(
            body.get("model", self.model_id), out["text"], out["finish_reason"],
            _usage(out["num_prompt_tokens"], out["num_generated_tokens"]))

    def handle_http(self, request: Dict[str, Any]):
        path, body = request["path"], request.get("body") or {}
        if path.endswith("/models"):
            return _models_list([self.model_id])
        chat = path.endswith("/chat/completions")
        if not chat and not path.endswith("/completions"):
            raise ValueError(f"unsupported path {path!r}")
        if isinstance(body, dict) and body.get("stream"):
            # streaming P/D rides the same device-plane handle as unary: the
            # decode replica pulls KV pages directly from the prefill replica
            # (~1 KB handle in the control message, no object-store hop) and
            # the decode stream is handed to the HTTP proxy before its first
            # frame — SSE frames never re-stream through this router
            prompt = (render_chat_template(body.get("messages", []))
                      if chat else body.get("prompt", ""))
            return self._stream_pd(prompt, body, chat)
        return self.chat(body) if chat else self.completions(body)

    def _stream_pd(self, prompt: str, body: Dict[str, Any], chat: bool):
        """Streaming P/D: dispatch prefill, then hand the decode replica's
        SSE stream to the HTTP proxy (StreamHandoff) BEFORE its first frame,
        so frames flow decode -> proxy -> client with no per-frame re-put
        through this router and nothing router-side on the first-content
        critical path — the disaggregated stream has the same hop count as
        the colocated one. The decode replica materializes the prefill
        future itself (overlapped with its own admission) and owns ALL
        failure handling: ``decode_stream`` re-prefills over the host path
        through its own prefill-pool handle on a device-plane failure —
        whether in the prefill result or mid-KV-pull — and resumes the same
        SSE stream, mirroring the unary path's fallback. Handing off before
        the first frame is therefore safe: there is nothing left for this
        router to splice."""
        pre_resp = self.prefill_handle.options(method_name="prefill").remote(
            prompt, body)

        def gen():
            from ray_tpu.serve.handle import StreamHandoff

            resp = self.decode_handle.options(
                method_name="decode_stream", stream=True).remote(
                    pre_resp, body, chat)
            ho = StreamHandoff.of(resp)
            if ho is not None:
                yield ho
                return
            # no transferable stream (local-testing handles, or the handoff
            # pin failed): relay frames through this process instead —
            # decode_stream's internal fallback still covers failures
            yield from resp

        return gen()


def _replica_actor_options(cfg: LLMConfig) -> Optional[Dict[str, Any]]:
    """ray_actor_options of an LLMServer replica. The caller's
    deployment_config["ray_actor_options"] wins; otherwise, on a cluster that
    advertises TPU chips, the replica asks for the pp×dp×ep×tp chips its engine
    mesh spans — a replica that asks for none is a CPU worker and would serve
    from the host. A cluster without chips (CPU test clusters) gets no request."""
    opts = cfg.deployment_config.get("ray_actor_options")
    if opts is not None:
        return opts
    import ray_tpu

    if not ray_tpu.is_initialized() or ray_tpu.cluster_resources().get("TPU", 0) <= 0:
        return None
    return {"num_tpus": (cfg.pipeline_parallel_size * cfg.data_parallel_size
                         * cfg.expert_parallel_size * cfg.tensor_parallel_size)}


def build_pd_openai_app(llm_config: LLMConfig, *, num_prefill: int = 1,
                        num_decode: int = 1, name_prefix: str = "llm-pd",
                        max_prefill: Optional[int] = None,
                        max_decode: Optional[int] = None,
                        ttft_slo_name: Optional[str] = None,
                        prefill_autoscaling=None, decode_autoscaling=None):
    """Prefill/decode-disaggregated serving app (reference build: P/D deployments).

    Each pool is an independently autoscaled multi-replica deployment — the
    two phases have different bottlenecks, so they get different signals:

    - the **prefill pool** scales off TTFT-SLO burn (``mode="slo"`` pinned to
      ``ttft_slo_name`` when given; register that SLO via
      ``telemetry.register_slo``). TTFT is prefill-bound, so burning the TTFT
      budget adds prefill replicas before touching decode.
    - the **decode pool** scales off live queue depth: decode holds each
      request for its whole generation, so backlog — not arrival rate — is
      the capacity signal.

    Autoscaling engages when ``max_prefill``/``max_decode`` exceed the
    ``num_*`` floors; either policy can be overridden wholesale with
    ``prefill_autoscaling``/``decode_autoscaling`` (AutoscalingConfig).
    Without caps the pools stay pinned at ``num_prefill``/``num_decode``.
    """
    from ray_tpu import serve
    from ray_tpu.serve.config import AutoscalingConfig

    if prefill_autoscaling is None and (max_prefill or 0) > num_prefill:
        prefill_autoscaling = AutoscalingConfig.for_slo(
            min_replicas=num_prefill, max_replicas=max_prefill,
            slo_names=[ttft_slo_name] if ttft_slo_name else None)
    if decode_autoscaling is None and (max_decode or 0) > num_decode:
        decode_autoscaling = AutoscalingConfig.for_slo(
            min_replicas=num_decode, max_replicas=max_decode)

    prefill = serve.deployment(LLMServer).options(
        name=f"{name_prefix}:prefill", num_replicas=num_prefill,
        max_ongoing_requests=32,
        ray_actor_options=_replica_actor_options(llm_config),
        autoscaling_config=prefill_autoscaling).bind(llm_config)
    decode = serve.deployment(LLMServer).options(
        name=f"{name_prefix}:decode", num_replicas=num_decode,
        max_ongoing_requests=64,
        ray_actor_options=_replica_actor_options(llm_config),
        autoscaling_config=decode_autoscaling).bind(
            llm_config, prefill_handle=prefill)
    router = serve.deployment(PDRouter).options(name=f"{name_prefix}-router")
    return router.bind(prefill, decode, llm_config.model_id)


def build_openai_app(llm_configs: List[LLMConfig], name_prefix: str = "llm"):
    """Build a Serve Application: OpenAIRouter ingress + one LLMServer per model.

    Reference builders/build_openai_app. Returns an Application for serve.run().
    """
    from ray_tpu import serve

    servers = {}
    for cfg in llm_configs:
        d = serve.deployment(LLMServer).options(
            name=f"{name_prefix}:{cfg.model_id}",
            num_replicas=cfg.deployment_config.get("num_replicas", 1),
            max_ongoing_requests=cfg.deployment_config.get("max_ongoing_requests", 64),
            ray_actor_options=_replica_actor_options(cfg),
        )
        servers[cfg.model_id] = d.bind(cfg)
    router = serve.deployment(OpenAIRouter).options(name=f"{name_prefix}-router")
    return router.bind(**servers)
