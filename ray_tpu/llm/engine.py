"""LLMEngine ABC + JaxLLMEngine: continuous batching on a device mesh.

Capability parity: reference python/ray/llm/_internal/serve/deployments/llm/
llm_engine.py:15 (``LLMEngine`` — start, generate stream) and vllm_engine.py:180
(``VLLMEngine`` — the continuous-batching loop lives in vLLM's AsyncLLMEngine).
Here the loop is explicit and TPU-shaped: a scheduler thread that (1) admits
waiting requests into free cache slots via a bucketed prefill jit, (2) advances
all active slots with one FUSED K-step decode+sample burst (the default mode —
K auto-tuned from the measured host round trip vs device step time, so one
host sync amortizes over K tokens), (3) streams token bursts out through
per-request queues. Scheduling is barrier-free continuous batching: requests
admit, retire, and abort at burst boundaries without draining the active
batch, and a per-slot step budget on device keeps one near-finished request
from collapsing the burst width for everyone. Every device computation has
static shapes, so after warmup the loop replays cached XLA executables only.
"""
from __future__ import annotations

import abc
import dataclasses
import itertools
import logging
import queue
import threading
import time
import uuid
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.util import telemetry
from ray_tpu.util.hot_path import hot_path

from .config import LLMConfig, SamplingParams
from . import model_runner
from .tokenizer import get_tokenizer

LOGGER = logging.getLogger(__name__)

_METRICS_WARN = None


def _metrics_guard_warn(where: str, e: BaseException) -> None:
    """Metrics must never take the engine down — but a broken exporter must
    not be INVISIBLE either (the PR 8 stale-registry bug hid behind exactly
    this pattern). One warning per 30s per call site, so one failing
    exporter does not mute the others' first report."""
    global _METRICS_WARN
    if _METRICS_WARN is None:
        from ray_tpu.util.logutil import LogThrottle

        _METRICS_WARN = LogThrottle(30.0)
    if _METRICS_WARN.ready(where):
        LOGGER.warning("engine telemetry export failed in %s (suppressed for "
                       "30s): %r", where, e)


# What one turn of the scheduler loop does between device calls, in order:
# admission (with aborts and the 5 s gauge refresh), the burst plan with block
# growth or preemption, building the arguments and enqueueing the decode
# program, the designed host sync (the token fetch), detokenising and handing
# tokens to the request threads, and the wait when no slot is active. The span
# names (profiler annotations + ring) tell a device idle gap's cause, e.g.
# `trace_reduce.reduce(planes, n, annotations=LOOP_SPANS)`; the same clock
# reads feed the always-on `loop_*_ns_total` counters of metrics().
LOOP_SPANS = ("llm.loop.admit", "llm.loop.grow_or_preempt", "llm.loop.dispatch",
              "llm.loop.fetch", "llm.loop.emit", "llm.loop.idle")
_ADMIT, _GROW, _DISPATCH, _FETCH, _EMIT, _IDLE = range(len(LOOP_SPANS))
_LOOP_COUNTERS = ("loop_admit_ns_total", "loop_grow_ns_total",
                  "loop_dispatch_ns_total", "loop_fetch_ns_total",
                  "loop_emit_ns_total", "loop_idle_ns_total")


@dataclasses.dataclass
class RequestOutput:
    """One streamed chunk: the tokens emitted since the previous chunk."""

    request_id: str
    token_ids: List[int]
    text: str = ""
    finished: bool = False
    finish_reason: Optional[str] = None  # "stop" | "length"
    num_prompt_tokens: int = 0
    num_generated_tokens: int = 0


class LLMEngine(abc.ABC):
    """Engine interface (reference llm_engine.py:15)."""

    @abc.abstractmethod
    def start(self) -> None: ...

    @abc.abstractmethod
    def generate(self, prompt: Any, params: SamplingParams, request_id: Optional[str] = None
                 ) -> Iterator[RequestOutput]: ...

    @abc.abstractmethod
    def shutdown(self) -> None: ...


def _pow2_floor(n: int) -> int:
    """Largest power of two <= n (n >= 1). Burst widths are quantized to
    powers of two: every distinct K is its own XLA trace, so this bounds the
    engine to log2(K_max)+1 compiled decode programs."""
    return 1 << (max(1, int(n)).bit_length() - 1)


class _Request:
    def __init__(self, req_id: str, prompt_ids: List[int], params: SamplingParams,
                 prefill_kv=None, arrival_wall_ns: Optional[int] = None):
        self.id = req_id
        self.prompt_ids = prompt_ids
        self.params = params
        self.out_queue: "queue.Queue[RequestOutput]" = queue.Queue()
        self.generated = 0
        self.slot = -1
        self.prefill_kv = prefill_kv  # (k, v, first_token): P/D-disagg transfer-in
        # paged streaming handoff: in-flight PagedKVFetch whose pages stream
        # concurrently with other requests' decode bursts; admission defers
        # until it is ready and resolves it into prefill_kv
        self.kv_fetch = None
        self.kv_fetch_error = None  # DevicePlaneError a failed fetch resolved to
        # completed fetch whose staging buffer prefill_kv still aliases;
        # recycled once the KV is installed (or the request fails)
        self.kv_staging = None
        self.kv_first_token = 0
        self.first_emitted = False  # first token streamed at arrival (TTFT
        # rides the handle); admission must not emit it again
        self.pending_text: List[int] = []  # undecoded ids (byte tokenizer is stateless)
        # prompt + every sampled token: recompute-preemption (paged pool
        # exhausted) re-prefills from this history so decoding continues exactly
        self.token_history: List[int] = list(prompt_ids)
        self.admitted_at = 0  # admission sequence number (preemption picks youngest)
        # request-lifecycle telemetry (queue -> prefill -> decode spans, TTFT,
        # tokens/s) + the prefix-cache evidence the Serve decode work needs:
        # how many prompt tokens the cache served vs how many prefill computed
        # time.time_ns() at which the HTTP proxy took the request in (None: it
        # did not come through the proxy); created - arrival is the way in
        self.arrival_wall_ns = arrival_wall_ns
        self.created_wall_ns = time.time_ns()
        self.created_perf_ns = time.perf_counter_ns()
        self.first_token_perf_ns = 0
        self.queue_recorded = False
        self.finish_recorded = False
        self.prefix_hit_tokens = -1  # -1 = no paged prefill ran (yet)
        self.prefill_tokens = 0  # tokens the model actually prefilled
        # request-scoped trace: captured at creation (the caller's thread —
        # serve replica / router with the propagated context); the scheduler
        # loop that records the queue/prefill/decode spans has no context
        try:
            from ray_tpu.util.tracing import current_trace_id

            self.trace_id = current_trace_id()
        # graftlint: allow[swallowed-exception] degrades to the coded fallback (self.trace_id = None) by design
        except Exception:
            self.trace_id = None


class JaxLLMEngine(LLMEngine):
    """Slot-based continuous batching over jitted prefill/decode (model_runner.py)."""

    def __init__(self, config: LLMConfig, params=None, mesh=None):
        self.config = config
        self.model_config = config.resolve_model_config()
        self.tokenizer = get_tokenizer(config.resolve_tokenizer_name())
        self._mesh = mesh
        self._params_in = params
        self._started = False
        self._shutdown = False
        self._waiting: "queue.Queue[_Request]" = queue.Queue()
        self._active: Dict[int, Optional[_Request]] = {}
        self._lock = threading.Lock()
        self._start_lock = threading.Lock()
        self._rng_lock = threading.Lock()
        self._loop_thread: Optional[threading.Thread] = None
        self._wakeup = threading.Event()
        self._admitting: Optional[_Request] = None  # mid-admission request
        # live requests by id (waiting or active); abort() only marks ids found
        # here, so a stale abort can never poison a later request reusing the id
        self._requests: Dict[str, "_Request"] = {}
        # request ids cancelled via abort(); acted on at admission (waiting) or
        # the next loop tick (active), cleared on request release
        self._aborted: set = set()
        self.state = None  # decode KV state, allocated on first decode admission
        # fused-decode fast path (resolved in start(); harmless defaults so an
        # unstarted engine's helpers — e.g. _propose_ngram in tests — work)
        self._fused_auto = False
        self._fused_fixed = 1
        self._fused_max = 1
        self._sync_target = 0.15
        self._host_rt_s = 0.0  # measured tiny dispatch+fetch round trip
        self._step_s = 0.0  # EWMA of per-decode-step device time
        self._k_seen: set = set()  # burst widths already compiled (first
        # burst at a new K carries compile time; skip it in the EWMA)
        self._prefill_per_tok_s = 0.0  # EWMA: prefill seconds per computed token
        self._last_tick_monotonic = time.monotonic()  # loop liveness (health)
        # metrics (scraped by LLMServer / autoscaling)
        self.num_pending = 0
        self.num_active = 0
        self.total_generated = 0
        self.num_preemptions = 0
        self.num_spec_drafted = 0
        self.num_spec_accepted = 0
        self.num_prefix_skipped = 0  # pay-or-skip gate declined the cache
        # monotonic integer counters, always on, returned by metrics(): time
        # and counts accumulated where the work happens, so that ratios over a
        # window (after - before) need no tracing. _counters is the loop
        # thread's alone; _ingress is written by request threads, under _lock.
        self._counters: Dict[str, int] = dict.fromkeys(_LOOP_COUNTERS + (
            "prefill_ns_total", "prefill_tokens_total", "prefill_calls_total",
            "decode_steps_total", "decode_slot_steps_total",
            "queue_wait_ns_total", "admitted_total"), 0)
        self._ingress = {"ingress_ns_total": 0, "ingress_requests_total": 0}
        # a lap ends where the next begins (util/telemetry.LapClock); the laps'
        # nanoseconds add into _counters
        self._clock = telemetry.LapClock(LOOP_SPANS, _LOOP_COUNTERS, "llm",
                                         self._counters)
        # P/D export bookkeeping (prefill side): (monotonic, key) per un-acked
        # KV export, LRU/TTL-pruned by _track_pd_export and the lazy prune
        # daemon; kept in sync with the device plane's own releases (consumer
        # acks, TTL sweeps) through a plane release listener
        self._pd_exports: List[Tuple[float, bytes]] = []
        self._pd_prune_thread: Optional[threading.Thread] = None
        self._pd_listener_registered = False

    # -- lifecycle ---------------------------------------------------------------
    def start(self) -> None:
        """Load + shard params (thread-safe, idempotent). The decode KV state and
        scheduler loop are allocated lazily on first decode use, so a dedicated
        prefill replica (P/D disaggregation) never pays for them."""
        with self._start_lock:
            if self._started:
                return
            from ray_tpu.usage import record_library_usage

            record_library_usage("llm")
            telemetry.compile_counters()  # the listener, once a process
            cfg = self.model_config
            c = self.config
            from ray_tpu.core.accelerators import check_worker_platform

            check_worker_platform()
            dev = jax.devices()[0]
            LOGGER.info("llm.engine model=%s: serving on platform=%s kind=%s "
                        "(%d devices)", c.model_id, dev.platform,
                        dev.device_kind, len(jax.devices()))
            if self._mesh is None:
                # pp*dp*ep*tp devices out of the local set (an engine may
                # intentionally use a subset, e.g. one replica per chip).
                from jax.sharding import Mesh

                pp = c.pipeline_parallel_size
                n = (pp * c.data_parallel_size * c.expert_parallel_size
                     * c.tensor_parallel_size)
                devs = jax.devices()
                if len(devs) < n:
                    raise ValueError(f"need {n} devices for pp×dp×ep×tp, have {len(devs)}")
                if pp > 1:
                    self._mesh = Mesh(
                        np.asarray(devs[:n]).reshape(
                            pp, c.data_parallel_size, c.expert_parallel_size,
                            c.tensor_parallel_size),
                        ("pp", "dp", "ep", "tp"),
                    )
                else:
                    self._mesh = Mesh(
                        np.asarray(devs[:n]).reshape(
                            c.data_parallel_size, c.expert_parallel_size,
                            c.tensor_parallel_size
                        ),
                        ("dp", "ep", "tp"),
                    )
            # fused decode is the default engine mode: explicit
            # num_decode_steps, else RAY_TPU_LLM_FUSED_STEPS (0 = auto-tune K
            # from measured host round trip vs device step time)
            from ray_tpu.config import CONFIG as _CFG

            k_cfg = c.resolve_decode_steps()
            self._fused_auto = k_cfg == 0
            # the cap bounds only the AUTO-tuned K (as documented); an
            # explicitly configured burst width is honored (pow2-quantized)
            self._fused_max = _pow2_floor(max(1, _CFG.llm_fused_steps_max))
            self._fused_fixed = 1 if self._fused_auto else _pow2_floor(k_cfg)
            self._sync_target = min(max(_CFG.llm_fused_sync_target, 0.01), 0.9)
            if c.pipeline_parallel_size > 1:
                if c.max_num_seqs % (c.pipeline_parallel_size
                                     * c.data_parallel_size):
                    raise ValueError(
                        "max_num_seqs must divide by pp*dp (slots shard over "
                        "dp replicas, then microbatch over pp stages)")
                if cfg.n_layers % c.pipeline_parallel_size:
                    raise ValueError("n_layers must divide by pipeline_parallel_size")
                if self._fused_auto or self._fused_fixed > 1:
                    # pp decode keeps per-step scheduling (microbatch ticks):
                    # downgrade cleanly instead of warning about a user knob
                    LOGGER.info(
                        "llm.engine model=%s: pipeline_parallel_size=%d keeps "
                        "per-step decode scheduling; fused multi-step decode "
                        "(num_decode_steps=%s) downgraded to 1",
                        c.model_id, c.pipeline_parallel_size,
                        "auto" if self._fused_auto else self._fused_fixed)
                    self._fused_auto = False
                    self._fused_fixed = 1
                    self._fused_max = 1
            if c.max_num_seqs % c.data_parallel_size:
                raise ValueError("max_num_seqs must be divisible by data_parallel_size")
            if c.kv_layout == "paged":
                if c.data_parallel_size > 1:
                    # paged ⊗ dp: per-replica pool partitions (paged.py dp
                    # section); the pool must split evenly across replicas
                    num_blocks = c.num_kv_blocks or (
                        c.max_num_seqs * c.max_model_len // c.kv_block_size)
                    if num_blocks % c.data_parallel_size:
                        raise ValueError(
                            f"num_kv_blocks ({num_blocks}) must divide by "
                            f"data_parallel_size ({c.data_parallel_size})")
                if c.max_model_len % c.kv_block_size:
                    raise ValueError("max_model_len must be a multiple of kv_block_size")
                if any(b % c.kv_block_size for b in c.buckets()):
                    raise ValueError(
                        "every prefill bucket must be a multiple of kv_block_size")
                if c.prefill_chunk and c.prefill_chunk % c.kv_block_size:
                    raise ValueError(
                        "prefill_chunk must be a multiple of kv_block_size "
                        "(chunked KV installs block-by-block)")
            elif c.kv_layout != "slot":
                raise ValueError(f"unknown kv_layout {c.kv_layout!r}")
            if c.num_speculative_tokens:
                if c.speculative_method != "ngram":
                    raise NotImplementedError(
                        f"speculative_method {c.speculative_method!r}: only "
                        "'ngram' (prompt lookup) is implemented")
            if c.prefill_chunk and c.max_model_len % c.prefill_chunk:
                # guarantees a chunk-padded prompt never exceeds max_model_len
                # (the block table / slot cache width)
                raise ValueError("max_model_len must be a multiple of prefill_chunk")
            if c.quantization:
                # validate BEFORE any checkpoint load: streaming a full model
                # onto devices just to reject the config string is hostile
                if c.quantization != "int8":
                    raise ValueError(
                        f"unknown quantization {c.quantization!r} (supported: int8)")
            if self._params_in is not None:
                self.params = model_runner.shard_params(self._params_in, cfg, self._mesh)
            else:
                from ray_tpu.models import checkpoint as ckpt_io

                if ckpt_io.looks_like_checkpoint_dir(c.model_source):
                    # real weights: stream safetensors straight into the sharded
                    # pytree (reference vllm_engine.py:180 — an engine that can't
                    # load a model is a demo)
                    self.params = ckpt_io.load_llama_params(
                        c.model_source, cfg, self._mesh,
                        rules=model_runner.infer_rules_for_mesh(self._mesh),
                        param_dtype=jnp.dtype(c.dtype))
                else:
                    self.params = llama_init_cached(cfg, c.dtype, self._mesh)
            self._params_in = None
            if c.quantization:
                from ray_tpu.ops.quant import quantize_llama_params

                # quantize on device AFTER sharding: per-output-channel int8
                # weights + scales; dequant fuses into each matmul's operand
                # read (ops/quant.py)
                self.params = jax.jit(quantize_llama_params)(self.params)
            self._active = {s: None for s in range(c.max_num_seqs)}
            self._admission_counter = itertools.count(1)
            if c.pipeline_parallel_size > 1:
                import functools

                self._decode_pp_jit = jax.jit(
                    functools.partial(model_runner.decode_step_pp,
                                      cfg=cfg, mesh=self._mesh),
                    donate_argnames=("state",))
                if c.num_speculative_tokens:
                    self._spec_pp_jit = jax.jit(
                        functools.partial(model_runner.spec_verify_step_pp,
                                          cfg=cfg, mesh=self._mesh),
                        donate_argnames=("state",))
            # graftlint: allow[lock-hygiene] one-time init under _start_lock, before any _next_rng caller exists; steady-state splits hold _rng_lock
            self._rng = jax.random.PRNGKey(0)
            # host mirrors of per-slot sampling params
            n = c.max_num_seqs
            self._temp = np.zeros((n,), np.float32)
            self._top_p = np.ones((n,), np.float32)
            self._top_k = np.zeros((n,), np.int32)
            self._last_tokens = np.zeros((n,), np.int32)
            self._started = True

    def _ensure_decode_started(self) -> None:
        """Allocate the decode KV state + scheduler loop on first decode use."""
        with self._start_lock:
            if self._loop_thread is not None:
                return
            c = self.config
            if self.state is None:
                if c.kv_layout == "paged":
                    from . import paged

                    num_blocks = c.num_kv_blocks or (
                        c.max_num_seqs * c.max_model_len // c.kv_block_size)
                    self._blocks = paged.make_block_manager(
                        num_blocks, c.kv_block_size,
                        c.max_model_len // c.kv_block_size, c.max_num_seqs,
                        dp=c.data_parallel_size,
                        enable_prefix_caching=c.enable_prefix_caching)
                    self._pops = paged.PagedOps(
                        self.model_config, self._mesh, c.max_num_seqs)
                    self.state = paged.init_paged_state(
                        self.model_config, c.max_num_seqs, c.max_model_len,
                        num_blocks, c.kv_block_size, self._mesh)
                else:
                    self.state = model_runner.init_state(
                        self.model_config, c.max_num_seqs, c.max_model_len, self._mesh)
            self._measure_host_rt()
            self._loop_thread = threading.Thread(target=self._loop, daemon=True,
                                                 name="llm-engine")
            self._loop_thread.start()

    # -- fused-burst auto-tune ----------------------------------------------------
    def _measure_host_rt(self, samples: int = 3) -> None:
        """Measure the fixed per-dispatch host round trip (dispatch + fetch of
        a scalar; about a millisecond on a local v5e chip). This is the cost
        fused bursts amortize, and the dispatch cost the prefix-cache
        pay-or-skip gate compares savings against. A device that cannot make
        this round trip cannot serve: the failure is the caller's to see."""
        x = jnp.zeros((), jnp.int32)
        np.asarray(x + 1)  # compile outside the timed region
        best = float("inf")
        for _ in range(samples):
            t0 = time.perf_counter()
            np.asarray(x + 1)
            best = min(best, time.perf_counter() - t0)
        self._host_rt_s = max(best, 1e-7)

    def decode_steps_target(self) -> int:
        """Current fused burst width target (power of two). Fixed K when
        configured; in auto mode, the smallest K that brings the host-sync
        share of a burst — rt/(rt + K*step) — down to the configured target
        fraction, from the measured round trip and device-step EWMA."""
        if not self._fused_auto:
            return self._fused_fixed
        rt, step = self._host_rt_s, self._step_s
        if rt <= 0 or step <= 0:
            return 1  # unmeasured yet: first bursts run per-step and probe
        f = self._sync_target
        need = rt * (1.0 - f) / (f * step)
        if need <= 1.0:
            return 1  # local chips: syncing every step is already cheap
        k = 1 << int(np.ceil(np.log2(need)))
        return max(1, min(k, self._fused_max))

    def _note_burst_device_wall(self, k: int, wall_s: float) -> None:
        """Fold one burst's dispatch->fetch wall time into the device-step
        EWMA (wall = rt + K*step). The first burst at each K carries its XLA
        compile and is skipped."""
        if k not in self._k_seen:
            self._k_seen.add(k)
            return
        est = max((wall_s - self._host_rt_s) / k, 1e-6)
        self._step_s = est if self._step_s <= 0 else (
            0.5 * est + 0.5 * self._step_s)

    def decode_host_sync_fraction(self) -> float:
        """Estimated share of decode wall time spent on the host round trip
        at the current burst width (the quantity auto-K minimizes)."""
        rt, step = self._host_rt_s, self._step_s
        if rt <= 0 or step <= 0:
            return 0.0
        k = self.decode_steps_target()
        return rt / (rt + k * step)

    def _slot_steps_left(self, req: "_Request") -> int:
        """Decode steps this request can still take: its remaining max_tokens
        budget capped by remaining KV room (both >= 1 for a live request —
        exhaustion finishes it in the previous burst's emit)."""
        next_write = len(req.prompt_ids) + req.generated - 1
        kv_room = (self.config.max_model_len - 1) - next_write
        budget = req.params.max_tokens - req.generated
        return max(1, min(kv_room, budget))

    def _burst_plan(self):
        """(k_steps, steps_left[slots]) for the next fused burst. K is the
        auto/fixed target capped by the LONGEST-running slot's budget (power
        of two); each slot's own budget rides to the device as steps_left, so
        short requests stop at their limit without capping the batch — the
        barrier the old min-over-slots burst width imposed."""
        n = self.config.max_num_seqs
        steps = np.ones((n,), np.int32)
        max_sl = 0
        for slot, req in self._active.items():
            if req is None:
                continue
            sl = self._slot_steps_left(req)
            steps[slot] = sl
            max_sl = max(max_sl, sl)
        k = _pow2_floor(min(self.decode_steps_target(), max(1, max_sl)))
        np.minimum(steps, k, out=steps)
        return k, steps

    def _next_rng(self):
        with self._rng_lock:
            self._rng, sub = jax.random.split(self._rng)
            return sub

    def _encode_prompt(self, prompt, params: SamplingParams) -> List[int]:
        """Tokenize + truncate so the generation fits max_model_len."""
        ids = self.tokenizer.encode(prompt) if isinstance(prompt, str) else list(prompt)
        limit = max(1, self.config.max_model_len - params.max_tokens)
        return ids[-limit:] if len(ids) > limit else ids

    def _pad_to_bucket(self, prompt_ids: List[int]):
        s_pad = next(b for b in self.config.buckets() if b >= len(prompt_ids))
        tokens = np.zeros((1, s_pad), np.int32)
        tokens[0, : len(prompt_ids)] = prompt_ids
        return tokens

    def shutdown(self) -> None:
        self._shutdown = True
        self._wakeup.set()
        if self._loop_thread is not None:
            self._loop_thread.join(timeout=5)

    # -- API ---------------------------------------------------------------------
    def generate(self, prompt, params: SamplingParams, request_id: Optional[str] = None,
                 arrival_wall_ns: Optional[int] = None) -> Iterator[RequestOutput]:
        """arrival_wall_ns: `time.time_ns()` at which the HTTP proxy took the
        request in, carried with it through router and replica; a request
        that did not come through the proxy has none and counts in neither
        ingress counter. The ingress counters subtract the proxy's wall clock
        from this process's, so they hold for a proxy and a replica on ONE
        node; across nodes the clocks' skew goes into the sum, or is cut off
        at zero."""
        self.start()
        self._ensure_decode_started()
        prompt_ids = self._encode_prompt(prompt, params)
        req = _Request(request_id or uuid.uuid4().hex, prompt_ids, params,
                       arrival_wall_ns=arrival_wall_ns)
        with self._lock:
            self.num_pending += 1
            self._requests[req.id] = req
            if req.arrival_wall_ns is not None:
                self._ingress["ingress_ns_total"] += max(
                    0, req.created_wall_ns - int(req.arrival_wall_ns))
                self._ingress["ingress_requests_total"] += 1
        self._waiting.put(req)
        self._wakeup.set()

        while True:
            out = req.out_queue.get()
            yield out
            if out.finished:
                return

    def abort(self, request_id: str) -> None:
        """Cancel a request (e.g. its SSE client disconnected): a waiting
        request is failed at admission; an active one frees its slot/KV blocks
        at the next scheduler tick instead of decoding to max_tokens.
        Reference: vllm engine abort_request semantics."""
        with self._lock:
            if request_id not in self._requests:
                return  # already finished (or unknown): nothing to cancel
            self._aborted.add(request_id)
        self._wakeup.set()

    def _finish_abort(self, req: "_Request") -> bool:
        """If `req` was cancelled, finish it now — abort chunk to the client,
        slot and paged blocks freed immediately — and return True. Called from
        every burst-boundary emit path, so a request cancelled while a fused
        burst is in flight on device stops emitting at the boundary (its
        burst tail is discarded) instead of streaming to max_tokens."""
        with self._lock:
            if req.id not in self._aborted:
                return False
        req.out_queue.put(RequestOutput(
            request_id=req.id, token_ids=[], finished=True,
            finish_reason="abort", num_prompt_tokens=len(req.prompt_ids),
            num_generated_tokens=req.generated))
        self._release(req)
        with self._lock:
            self._aborted.discard(req.id)
        return True

    def _process_aborts(self) -> None:
        """Release active slots whose request was aborted (called every tick)."""
        with self._lock:
            if not self._aborted:
                return
        for slot, req in list(self._active.items()):
            if req is not None:
                self._finish_abort(req)

    # -- P/D disaggregation (reference: prefill_decode_disagg deployments) ---------
    def prefill_only(self, prompt, params: SamplingParams,
                     force_host: bool = False) -> Dict[str, Any]:
        """Run prefill and return transferable KV + the sampled first token.
        Used by prefill replicas; the result feeds generate_from_prefill on a
        decode replica. With the device plane up, the KV stays device-resident
        here and the decode replica pulls it device-to-device (DCN on pods —
        reference: NCCL KV handoff in prefill_decode_disagg); only a ~1 KB handle
        rides the control plane. Otherwise the KV travels as host arrays through
        the object store. Does NOT allocate the decode state — prefill replicas
        stay KV-cache-free."""
        self.start()
        prompt_ids = self._encode_prompt(prompt, params)
        # chunk-aware: a P/D prefill replica is exactly where long-prompt
        # activation memory must stay bounded
        k, v, last_logits = self._prefill_kv_tensors(prompt_ids)
        tok = int(model_runner.sample_tokens(
            self._next_rng(), last_logits[None, :],
            jnp.asarray([params.temperature], jnp.float32),
            jnp.asarray([params.top_p], jnp.float32),
            jnp.asarray([params.top_k], jnp.int32),
        )[0])
        out = {"prompt_ids": prompt_ids, "first_token": tok}
        # pre-rendered first-token text: lets the P/D router mint the first
        # SSE content frame the moment this result lands, without waiting for
        # the decode replica's stream to start (TTFT rides prefill alone).
        # Stop tokens emit no content and a token that decodes to a partial
        # UTF-8 codepoint can't be rendered alone — both leave first_text
        # unset and the router falls back to relaying the decode stream.
        stops = params.stop_token_ids or [self.tokenizer.eos_token_id]
        if tok not in stops:
            txt = self.tokenizer.decode([tok])
            if txt and not txt.endswith("�"):
                out["first_text"] = txt
        from ray_tpu.config import CONFIG as _CFG
        from ray_tpu.core import device_plane as _dp

        if self.config.kv_layout == "paged":
            # ship only the block-aligned prefix the decode side installs —
            # the bucket-pad tail is attention-masked garbage it re-pads anyway
            from .paged import trim_kv_for_transfer

            k, v = trim_kv_for_transfer(k, v, len(prompt_ids),
                                        self.config.kv_block_size)
        dp = _dp.plane()
        use_paged = bool(_CFG.pd_paged) and dp.paged_available
        if not force_host and (use_paged or dp.available):
            # plane-level ttl: backstop for a decode replica that crashes
            # before acking (the engine's own tracker prunes sooner)
            if use_paged:
                # block-addressable region on the striped data plane: the
                # decode side pulls it page-by-page over multiple streams,
                # overlapped with its decode bursts
                handle = dp.export_paged({"k": k, "v": v},
                                         ttl_s=_CFG.pd_export_ttl_s,
                                         page_bytes=_CFG.pd_page_bytes)
            else:
                handle = dp.export({"k": k, "v": v}, ttl_s=_CFG.pd_export_ttl_s)
            self._track_pd_export(handle.key)
            out["kv_handle"] = handle
            out["kv_key"] = handle.key.hex()
        else:
            out["k"] = np.asarray(k)
            out["v"] = np.asarray(v)
        return out

    def _track_pd_export(self, key: bytes, max_live: int = None,
                         ttl_s: float = None) -> None:
        """Exports pin device KV until the decode side's pull acks (fetch
        release=True); this LRU/TTL prune is the backstop for crashed consumers.
        Guarded by the engine lock: prefill and decode-ack run on different
        request threads. Defaults from CONFIG: pd_export_max_live, and half of
        pd_export_ttl_s so the engine prunes before the plane-level backstop."""
        import time as _time

        from ray_tpu.config import CONFIG as _CFG
        from ray_tpu.core import device_plane as _dp

        if max_live is None:
            max_live = _CFG.pd_export_max_live
        if ttl_s is None:
            ttl_s = _CFG.pd_export_ttl_s / 2
        self._ensure_pd_release_listener()
        now = _time.monotonic()
        stale = []
        with self._lock:
            pending = self._pd_exports
            pending.append((now, key))
            while pending and (len(pending) > max_live or now - pending[0][0] > ttl_s):
                stale.append(pending.pop(0)[1])
            if self._pd_prune_thread is None:
                # TTL enforcement can't depend on the NEXT prefill arriving —
                # a crashed consumer with no follow-on traffic would pin KV
                # forever. A lazy daemon sweeps on a timer.
                self._pd_prune_thread = threading.Thread(
                    target=self._pd_prune_loop, daemon=True,
                    name="rt-pd-export-prune")
                self._pd_prune_thread.start()
        for old in stale:
            _dp.plane().release(old)

    def _ensure_pd_release_listener(self) -> None:
        """Keep _pd_exports in lockstep with the device plane: consumer acks
        ride the arm channel straight to the plane (pool routing cannot
        address 'the replica that prefilled'), so the engine learns about
        them through the plane's release listener rather than polling. A
        WeakMethod keeps retired engines collectable — the plane is a
        process singleton."""
        if self._pd_listener_registered:
            return
        import weakref

        from ray_tpu.core import device_plane as _dp

        with self._lock:
            if self._pd_listener_registered:
                return
            self._pd_listener_registered = True
        ref = weakref.WeakMethod(self._on_pd_export_released)

        def _cb(key, _ref=ref):
            m = _ref()
            if m is not None:
                m(key)

        _dp.plane().add_release_listener(_cb)

    def _on_pd_export_released(self, key: bytes) -> None:
        with self._lock:
            if self._pd_exports:
                self._pd_exports[:] = [e for e in self._pd_exports
                                       if e[1] != key]

    def _pd_prune_loop(self, interval_s: float = 30.0,
                       ttl_s: float = None) -> None:
        import time as _time

        from ray_tpu.config import CONFIG as _CFG
        from ray_tpu.core import device_plane as _dp

        if ttl_s is None:
            ttl_s = _CFG.pd_export_ttl_s / 2

        while not self._shutdown:
            _time.sleep(interval_s)
            now = _time.monotonic()
            stale = []
            with self._lock:
                pending = self._pd_exports
                while pending and now - pending[0][0] > ttl_s:
                    stale.append(pending.pop(0)[1])
            for old in stale:
                _dp.plane().release(old)

    def release_prefill_export(self, key_hex: str) -> None:
        """Decode-side ack: the KV for this prefill was pulled (or abandoned)."""
        from ray_tpu.core import device_plane as _dp

        key = bytes.fromhex(key_hex)
        _dp.plane().release(key)
        with self._lock:
            if self._pd_exports:
                self._pd_exports[:] = [e for e in self._pd_exports
                                       if e[1] != key]

    def generate_from_prefill(self, prefill_result: Dict[str, Any],
                              params: SamplingParams,
                              request_id: Optional[str] = None
                              ) -> Iterator[RequestOutput]:
        """Continue decoding from a transferred prefill (decode replica side).

        The device-plane handle is validated EAGERLY (not at first next()) so
        a dead export raises here — where the P/D router can still fall back
        to the host path — rather than mid-stream.

        Paged handles stream: the first token (sampled prefill-side, riding
        the ~1 KB handle) is emitted immediately, the KV pages pull over
        multiple streams concurrently with the active batch's decode bursts,
        and the request admits at a burst boundary once its pages have
        landed. A mid-transfer failure surfaces as a typed DevicePlaneError
        from the stream, which the PDRouter converts into its host-fallback
        replay."""
        self.start()
        self._ensure_decode_started()
        fetch = None
        if "kv_handle" in prefill_result:
            from ray_tpu.core import device_plane as _dp

            handle = prefill_result["kv_handle"]
            if isinstance(handle, _dp.PagedKVHandle):
                # raises DevicePlaneError here if the export is already gone
                fetch = _dp.plane().fetch_paged(handle, release=True,
                                                on_done=self._wakeup.set)
                req = _Request(request_id or uuid.uuid4().hex,
                               list(prefill_result["prompt_ids"]), params)
                req.kv_fetch = fetch
                req.kv_first_token = int(prefill_result["first_token"])
            else:
                t0_wall, t0_perf = time.time_ns(), time.perf_counter_ns()
                kv = _dp.plane().fetch(handle, release=True)
                self._record_kv_handoff_raw(
                    handle.nbytes, (time.perf_counter_ns() - t0_perf) / 1e9,
                    t0_wall, mode="monolithic")
                req = _Request(
                    request_id or uuid.uuid4().hex,
                    list(prefill_result["prompt_ids"]), params,
                    prefill_kv=(kv["k"], kv["v"],
                                int(prefill_result["first_token"])),
                )
        else:
            req = _Request(
                request_id or uuid.uuid4().hex,
                list(prefill_result["prompt_ids"]), params,
                prefill_kv=(prefill_result["k"], prefill_result["v"],
                            int(prefill_result["first_token"])),
            )
        with self._lock:
            self.num_pending += 1
            self._requests[req.id] = req
        if fetch is not None and self._emit_prefill_first_token(req):
            pass  # finished on its first token: never queued, fetch abandoned
        else:
            self._waiting.put(req)
            self._wakeup.set()

        def _stream() -> Iterator[RequestOutput]:
            while True:
                out = req.out_queue.get()
                if out.finish_reason == "kv_transfer":
                    from ray_tpu.core.device_plane import DevicePlaneError

                    err = req.kv_fetch_error if req.kv_fetch_error is not None \
                        else DevicePlaneError("paged KV transfer failed")
                    raise err
                yield out
                if out.finished:
                    return

        return _stream()

    def _emit_prefill_first_token(self, req: _Request) -> bool:
        """Paged P/D handoff: stream the prefill-sampled first token NOW —
        TTFT rides the handle, not the KV payload. Returns True when that
        token already finishes the request (stop token or max_tokens == 1);
        it then never enters the waiting queue and the in-flight fetch is
        abandoned (with a release ack, so the producer unpins)."""
        tok = req.kv_first_token
        req.generated = 1
        req.token_history.append(tok)
        req.first_emitted = True
        self._record_first_token(req)
        with self._lock:
            self.total_generated += 1
        stops = req.params.stop_token_ids or [self.tokenizer.eos_token_id]
        finished, reason = False, None
        if tok in stops:
            finished, reason = True, "stop"
        elif req.generated >= req.params.max_tokens:
            finished, reason = True, "length"
        emit_ids = [] if reason == "stop" else [tok]
        req.out_queue.put(RequestOutput(
            request_id=req.id, token_ids=emit_ids,
            text=self.tokenizer.decode(emit_ids) if emit_ids else "",
            finished=finished, finish_reason=reason,
            num_prompt_tokens=len(req.prompt_ids), num_generated_tokens=1,
        ))
        if finished:
            req.kv_fetch.cancel()
            req.kv_fetch = None
            self._record_finish(req)
            with self._lock:
                self.num_pending -= 1
                self._requests.pop(req.id, None)
                self._aborted.discard(req.id)
        return finished

    def generate_sync(self, prompt, params: SamplingParams,
                      arrival_wall_ns: Optional[int] = None) -> RequestOutput:
        """Collect the full generation into one RequestOutput."""
        ids: List[int] = []
        last = None
        for chunk in self.generate(prompt, params, arrival_wall_ns=arrival_wall_ns):
            ids.extend(chunk.token_ids)
            last = chunk
        return RequestOutput(
            request_id=last.request_id,
            token_ids=ids,
            text=self.tokenizer.decode(ids),
            finished=True,
            finish_reason=last.finish_reason,
            num_prompt_tokens=last.num_prompt_tokens,
            num_generated_tokens=len(ids),
        )

    def metrics(self) -> Dict[str, Any]:
        """Engine health + paged-KV performance counters (reference: vllm
        engine stats — pool occupancy, prefix-cache hits, preemptions — the
        numbers that validate the paged design under load).

        The `*_total` keys are monotonic integers counted where the work
        happens (the scheduler loop's phases, prefill, decode steps and
        slot-steps, queue wait, the way in, compiles of this process): read
        them before and after a window and divide the differences.
        `decode_device_step_ms`, `decode_host_rt_ms` and
        `decode_host_sync_fraction` are ESTIMATES from the host clock (wall
        time of dispatch-to-fetch minus a measured round trip, smoothed): the
        burst planner steers by them; they are not device times."""
        out = {
            "num_pending": self.num_pending,
            "num_active": self.num_active,
            "total_generated": self.total_generated,
            "num_preemptions": self.num_preemptions,
            "num_spec_drafted": self.num_spec_drafted,
            "num_spec_accepted": self.num_spec_accepted,
            "num_prefix_skipped": self.num_prefix_skipped,
            # P/D: device-plane KV exports this engine still pins (leak probe
            # for the chaos gate — consumer acks must drain it, not the TTL)
            "pd_exports_live": len(self._pd_exports),
            # fused fast path: current burst width and where the decode wall
            # time goes (the quantity auto-K minimizes; the bench gates on it)
            "decode_fused_steps": self.decode_steps_target(),
            "decode_host_sync_fraction": round(
                self.decode_host_sync_fraction(), 4),
            "decode_host_rt_ms": round(self._host_rt_s * 1e3, 4),
            "decode_device_step_ms": round(self._step_s * 1e3, 4),
        }
        out.update(self._counters)
        out.update(self._ingress)
        out.update(telemetry.compile_counters())
        blocks = getattr(self, "_blocks", None)
        if blocks is not None:
            total = blocks.total_blocks
            free = blocks.num_free
            out.update({
                "kv_blocks_total": total,
                "kv_pool_occupancy": (total - free) / total if total else 0.0,
                "prefix_cache_hit_tokens": blocks.hit_tokens,
                "prefix_cached_blocks": len(blocks.cached),
            })
        self._export_metrics(out)
        return out

    def device_report(self) -> Dict[str, Any]:
        """Where this engine landed and what it holds there (a started
        engine; not part of metrics(): the scheduler loop calls that)."""
        devices = list(self._mesh.devices.flat)
        return {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "param_dtype": str(jax.tree.leaves(self.params)[0].dtype),
            "vocab_size": self.model_config.vocab_size,
            "peak_bytes_in_use": [int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)) for d in devices],
        }

    def _export_metrics(self, snap: Dict[str, Any]) -> None:
        """Mirror the engine counters into the cluster metric registry so they
        ride /metrics -> Prometheus/Grafana (reference: vllm stat loggers
        feeding Ray metrics)."""
        try:
            from ray_tpu.util.metrics import Gauge

            tags = {"model": str(self.config.model_id)}
            for name, value in snap.items():
                if not isinstance(value, (int, float)):
                    continue
                # module-level cache: engines share one gauge per metric name
                # (the model tag separates them); per-engine gauges would
                # evict each other from the process registry
                g = _PROM_GAUGES.get(name)
                if g is None:
                    g = Gauge(f"llm_{name}", f"engine {name}", tag_keys=("model",))
                    _PROM_GAUGES[name] = g
                g.set(float(value), tags=tags)
        except Exception as e:
            _metrics_guard_warn("_export_metrics", e)

    # -- request-lifecycle telemetry ----------------------------------------------
    def _model_tag(self) -> Dict[str, str]:
        return {"model": str(self.config.model_id)}

    @staticmethod
    def _prefill_tokens_of(req: _Request) -> int:
        """Tokens the model actually prefilled. Only the paged path tracks a
        cached/computed split (prefix_hit_tokens >= 0); every other layout
        prefills the whole prompt."""
        if req.prefix_hit_tokens >= 0:
            return req.prefill_tokens
        return len(req.prompt_ids)

    def _record_prefill(self, req: _Request, t_admit_perf: int) -> None:
        """Prefill-phase signals, recorded once per successful admission:
        latency, computed-vs-cached token counts, and the per-request
        hit/miss evidence behind prefix_cache_ttft_speedup (why does the
        cache win or lose? the spans now say).

        Guarded like _export_metrics: these run inside the scheduler loop,
        and metrics must never take the engine down."""
        try:
            self._record_prefill_inner(req, t_admit_perf)
        except Exception as e:
            _metrics_guard_warn("_record_prefill", e)

    def _record_prefill_inner(self, req: _Request, t_admit_perf: int) -> None:
        dur = time.perf_counter_ns() - t_admit_perf
        self._counters["prefill_ns_total"] += dur
        self._counters["prefill_tokens_total"] += self._prefill_tokens_of(req)
        self._counters["prefill_calls_total"] += 1
        # per-token prefill cost EWMA (dispatch round trip subtracted): the
        # prefix-cache pay-or-skip gate's estimate of what a cached token
        # saves. A first-compile sample inflates it, which only biases the
        # gate toward USING the cache — the safe direction — and washes out.
        computed = max(1, self._prefill_tokens_of(req))
        per_tok = max((dur / 1e9 - self._host_rt_s) / computed, 1e-9)
        self._prefill_per_tok_s = per_tok if self._prefill_per_tok_s <= 0 else (
            0.3 * per_tok + 0.7 * self._prefill_per_tok_s)
        tags = self._model_tag()
        if req.prefix_hit_tokens >= 0:  # a paged prefill ran for this admission
            name = ("llm_prefix_cache_hits_total" if req.prefix_hit_tokens > 0
                    else "llm_prefix_cache_misses_total")
            telemetry.get_counter(
                name, "paged prefills that hit/missed the prefix cache",
                tag_keys=("model",)).inc(1.0, tags=tags)
        if telemetry.enabled():
            telemetry.complete(
                "llm.prefill", "llm",
                req.created_wall_ns + (t_admit_perf - req.created_perf_ns),
                dur, request_id=req.id, prompt_tokens=len(req.prompt_ids),
                prefix_hit_tokens=max(req.prefix_hit_tokens, 0),
                prefill_tokens=self._prefill_tokens_of(req),
                cache_hit=req.prefix_hit_tokens > 0,
                trace_id=req.trace_id)

    def _record_kv_handoff(self, fetch) -> None:
        self._record_kv_handoff_raw(fetch.nbytes, fetch.dur_s or 0.0,
                                    fetch.t0_wall_ns, mode="paged",
                                    pages=fetch.n_pages, streams=fetch.streams)

    def _record_kv_handoff_raw(self, nbytes: int, dur_s: float,
                               t0_wall_ns: int, mode: str, pages: int = 1,
                               streams: int = 1) -> None:
        """P/D KV handoff signals: per-transfer GB/s histogram (surfaced in
        cluster_status()["llm"]) + an llm.kv_handoff span covering the
        transfer wall time."""
        try:
            if dur_s <= 0 or nbytes <= 0:
                return
            gbps = nbytes / dur_s / 1e9
            tags = dict(self._model_tag(), mode=mode)
            telemetry.get_histogram(
                "llm_kv_handoff_gbps",
                "P/D KV handoff throughput per transfer (GB/s)",
                tag_keys=("model", "mode"),
                boundaries=[0.1, 0.25, 0.5, 1, 2, 4, 8, 16, 32]).observe(
                gbps, tags=tags)
            if telemetry.enabled():
                telemetry.complete(
                    "llm.kv_handoff", "llm", t0_wall_ns, int(dur_s * 1e9),
                    bytes=nbytes, pages=pages, streams=streams, mode=mode,
                    gbps=round(gbps, 3))
        except Exception as e:
            _metrics_guard_warn("_record_kv_handoff", e)

    def _record_first_token(self, req: _Request) -> None:
        req.first_token_perf_ns = time.perf_counter_ns()
        try:
            ttft_s = (req.first_token_perf_ns - req.created_perf_ns) / 1e9
            telemetry.get_histogram(
                "llm_ttft_seconds", "engine time-to-first-token",
                tag_keys=("model",)).observe(ttft_s, tags=self._model_tag())
        except Exception as e:
            _metrics_guard_warn("_record_first_token", e)

    def _record_finish(self, req: _Request) -> None:
        if req.first_token_perf_ns == 0 or req.finish_recorded:
            return
        req.finish_recorded = True
        try:
            self._record_finish_inner(req)
        except Exception as e:
            _metrics_guard_warn("_record_finish", e)

    def _record_finish_inner(self, req: _Request) -> None:
        now = time.perf_counter_ns()
        decode_ns = now - req.first_token_perf_ns
        decode_s = decode_ns / 1e9
        # decode throughput = tokens AFTER the first / decode time: dividing
        # by the full lifetime would fold queue+prefill in and understate the
        # engine exactly when it is loaded. Single-token requests have no
        # decode phase to rate.
        rate = ((req.generated - 1) / decode_s
                if decode_s > 0 and req.generated > 1 else None)
        if rate is not None:
            telemetry.get_histogram(
                "llm_tokens_per_s", "per-request decode throughput",
                tag_keys=("model",),
                boundaries=[1, 5, 10, 25, 50, 100, 250, 500, 1000]).observe(
                rate, tags=self._model_tag())
        if telemetry.enabled():
            wall_first = req.created_wall_ns + (req.first_token_perf_ns
                                                - req.created_perf_ns)
            telemetry.complete(
                "llm.decode", "llm", wall_first, decode_ns,
                request_id=req.id, generated=req.generated,
                prompt_tokens=len(req.prompt_ids),
                prefix_hit_tokens=max(req.prefix_hit_tokens, 0),
                prefill_tokens=self._prefill_tokens_of(req),
                tokens_per_s=round(rate, 2) if rate is not None else 0.0,
                trace_id=req.trace_id)

    # -- scheduler loop ------------------------------------------------------------
    def _free_slots(self) -> List[int]:
        free = [s for s, r in self._active.items() if r is None]
        c = self.config
        if c.kv_layout == "paged" and c.data_parallel_size > 1 and free:
            # admit into the dp replica with the most free blocks first (one
            # full partition must not head-of-line-block admission to others)
            free.sort(key=lambda s: -self._blocks.num_free_for(s))
        return free

    def _admit(self) -> None:
        cfg, c = self.model_config, self.config
        # paged P/D requests whose pages are still streaming: skipped this
        # pass, re-queued on exit so they admit at a later burst boundary —
        # their transfer overlaps the active batch's decode bursts instead of
        # head-of-line-blocking admission
        deferred: List[_Request] = []
        try:
            self._admit_inner(cfg, c, deferred)
        finally:
            for r in deferred:
                self._waiting.put(r)

    def _admit_inner(self, cfg, c, deferred: List["_Request"]) -> None:
        for slot in self._free_slots():
            try:
                req = self._waiting.get_nowait()
            except queue.Empty:
                return
            with self._lock:
                was_aborted = req.id in self._aborted
                self._aborted.discard(req.id)
            if was_aborted:
                if req.kv_fetch is not None:
                    req.kv_fetch.cancel()
                    req.kv_fetch = None
                self._fail_request(req, len(req.prompt_ids), "abort")
                continue
            if req.kv_fetch is not None:
                err = req.kv_fetch.failed()
                if err is not None:
                    # mid-transfer failure (producer died, export retracted,
                    # deadline): typed finish — generate_from_prefill's stream
                    # re-raises it as DevicePlaneError for the router fallback
                    req.kv_fetch_error = err
                    req.kv_fetch = None
                    self._fail_request(req, len(req.prompt_ids), "kv_transfer")
                    continue
                if not req.kv_fetch.ready():
                    deferred.append(req)
                    continue
                fetch, req.kv_fetch = req.kv_fetch, None
                kv = fetch.result()
                req.prefill_kv = (kv["k"], kv["v"], req.kv_first_token)
                req.kv_staging = fetch
                self._record_kv_handoff(fetch)
            # visible to the loop's crash handler: this request is in neither
            # _waiting nor _active right now, and must still be failed on error
            self._admitting = req
            t_admit_perf = time.perf_counter_ns()
            if not req.queue_recorded:
                # queue span: creation to FIRST admission attempt, once — a
                # request requeued on pool exhaustion (or preempted) must not
                # emit a later, longer llm.queue span. Marked even when
                # telemetry is off, so mid-flight enabling can't fabricate
                # queue time that includes a previous admission's decode.
                req.queue_recorded = True
                self._counters["queue_wait_ns_total"] += (
                    t_admit_perf - req.created_perf_ns)
                self._counters["admitted_total"] += 1
                if telemetry.enabled():
                    telemetry.complete(
                        "llm.queue", "llm", req.created_wall_ns,
                        t_admit_perf - req.created_perf_ns, request_id=req.id,
                        prompt_tokens=len(req.prompt_ids),
                        trace_id=req.trace_id)
            p = req.params
            if req.prefill_kv is not None:
                # P/D disaggregation: KV computed by a prefill replica; install it
                # and emit the first token the prefill side already sampled.
                k, v, tok = req.prefill_kv
                if c.kv_layout == "paged":
                    if not self._admit_paged_kv(req, slot, jnp.asarray(k), jnp.asarray(v)):
                        self._admitting = None
                        return  # pool full: req (prefill_kv intact) requeued
                elif k.shape[2] > c.max_model_len:
                    # transfer padded past this engine's slot width: fail just
                    # this request (install_kv would crash the whole loop)
                    self._fail_request(req, len(req.prompt_ids))
                    if req.kv_staging is not None:
                        req.kv_staging.recycle()
                        req.kv_staging = None
                    self._admitting = None
                    continue
                else:
                    self.state = model_runner.install_kv(
                        self.state, jnp.asarray(k), jnp.asarray(v),
                        jnp.int32(len(req.prompt_ids)), jnp.int32(slot),
                    )
                req.prefill_kv = None
                if req.kv_staging is not None:
                    # jnp.asarray copied the KV out of the staging buffer
                    # above; hand it back for the next handoff's fetch
                    req.kv_staging.recycle()
                    req.kv_staging = None
            elif c.kv_layout == "paged":
                tok = self._prefill_paged(req, slot)
                if tok is None:
                    self._admitting = None
                    return  # pool full: requeued, stop admitting
            elif c.prefill_chunk and len(req.prompt_ids) > c.prefill_chunk:
                # chunked prefill works for the slot layout too: bound peak
                # activation memory, then install the assembled KV at once
                k, v, last_logits = self._prefill_kv_tensors(req.prompt_ids)
                self.state = model_runner.install_kv(
                    self.state, k, v, jnp.int32(len(req.prompt_ids)), jnp.int32(slot))
                tok = self._sample_one(last_logits, p)
            else:
                tokens = self._pad_to_bucket(req.prompt_ids)
                self.state, last_logits = model_runner.prefill(
                    self.params, self.state, jnp.asarray(tokens),
                    jnp.int32(len(req.prompt_ids)), jnp.int32(slot), cfg,
                )
                tok = self._sample_one(last_logits, p)
            self._record_prefill(req, t_admit_perf)
            req.slot = slot
            req.admitted_at = next(self._admission_counter)
            self._active[slot] = req
            self._temp[slot], self._top_p[slot], self._top_k[slot] = (
                p.temperature, p.top_p, p.top_k)
            self._last_tokens[slot] = tok
            with self._lock:
                self.num_pending -= 1
                self.num_active += 1
            self._admitting = None
            if not req.first_emitted:
                self._emit(req, tok)

    def _sample_one(self, last_logits, p: SamplingParams) -> int:
        return int(model_runner.sample_tokens(
            self._next_rng(), last_logits[None, :],
            jnp.asarray([p.temperature], jnp.float32),
            jnp.asarray([p.top_p], jnp.float32),
            jnp.asarray([p.top_k], jnp.int32),
        )[0])

    # -- paged KV (reference: vLLM PagedAttention block tables) --------------------
    def _fail_request(self, req: _Request, n: int, reason: str = "length") -> None:
        req.out_queue.put(RequestOutput(
            request_id=req.id, token_ids=[], finished=True,
            finish_reason=reason, num_prompt_tokens=n,
            num_generated_tokens=req.generated))
        with self._lock:
            self.num_pending -= 1
            self._requests.pop(req.id, None)
            self._aborted.discard(req.id)

    def _install_paged(self, req: _Request, slot: int, k, v, n: int) -> Optional[bool]:
        """Allocate blocks for [L,1,S_pad,...] prefill KV and install it.
        True = installed; False = pool busy (req requeued by the CALLER);
        None = can never fit (request failed here)."""
        c = self.config
        s_pad = k.shape[2]
        needed = self._blocks.blocks_needed(max(n + 1, s_pad))
        if needed > self._blocks.max_fit(slot):
            # exceeds this slot's pool (its dp replica's partition) OR the
            # per-slot table width (e.g. a P/D transfer padded past the decode
            # engine's max_model_len): can never fit, so fail instead of
            # requeueing forever
            self._fail_request(req, n)
            return None
        if not self._blocks.can_allocate_for(slot, needed):
            return False
        block_ids = self._blocks.allocate(slot, needed)
        if s_pad < needed * c.kv_block_size:
            extra = needed * c.kv_block_size - s_pad
            k = jnp.pad(k, ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
        self.state = self._pops.install_prefill(
            self.state, k, v, jnp.asarray(block_ids, jnp.int32), jnp.int32(n),
            jnp.int32(slot), n_blocks=needed)
        return True

    def _prefill_kv_tensors(self, prompt: List[int]):
        """(k, v, last_logits) for a prompt — whole-bucket or chunked prefill."""
        from . import paged

        cfg, c = self.model_config, self.config
        n = len(prompt)
        chunk = c.prefill_chunk
        if chunk and n > chunk:
            return paged.chunked_prefill(self.params, prompt, cfg, chunk)
        s_pad = next(b for b in c.buckets() if b >= n)
        tokens = np.zeros((1, s_pad), np.int32)
        tokens[0, :n] = prompt
        return model_runner.prefill_detached(
            self.params, jnp.asarray(tokens), jnp.int32(n), cfg)

    def _prefill_paged(self, req: _Request, slot: int) -> Optional[int]:
        """Prefill into allocated blocks; None = not admitted (requeued/failed).
        With prefix caching (reference: vLLM automatic prefix caching) a prompt
        sharing full leading blocks with an earlier one skips their
        recomputation: cached blocks join the slot's table by reference and the
        model runs only over the uncached suffix."""
        prompt = req.token_history if req.generated else req.prompt_ids
        n = len(prompt)
        chunk = self.config.prefill_chunk
        bs = self.config.kv_block_size
        cached_ids: List[int] = []
        min_hit = self._prefix_min_hit_tokens()
        max_hit = ((n - 1) // bs) * bs  # full blocks; last token always computed
        if self.config.enable_prefix_caching and max_hit >= bs:
            # pay-or-skip (the prefix_cache_ttft_speedup:0.95 fix): use the
            # cache only when the predicted compute saving — hit tokens x the
            # measured per-token prefill time — clears the measured dispatch
            # round trip. Where the round trip outweighs the prefill FLOPs a
            # few cached blocks save, hashing/refcounting them is pure
            # overhead; skip the whole machinery then.
            if max_hit < min_hit:
                self.num_prefix_skipped += 1
            else:
                cached_ids = self._blocks.match_prefix(slot, prompt)
                if cached_ids and len(cached_ids) * bs < min_hit:
                    self._blocks.release(slot)  # detach: hit too small to pay
                    cached_ids = []
                    self.num_prefix_skipped += 1
        # telemetry groundwork for the prefix-cache speedup mystery: record
        # what the cache SERVED vs what the model computed, per request
        req.prefix_hit_tokens = len(cached_ids) * bs
        req.prefill_tokens = n - req.prefix_hit_tokens
        if cached_ids:
            suffix_len = n - len(cached_ids) * bs
            if not chunk or suffix_len <= chunk:
                # cached context + one whole-bucket suffix prefill
                return self._prefill_with_prefix(req, slot, prompt, cached_ids)
            # suffix still too long for one pass: fall back to chunked prefill
            # (no context support there yet) but release the attached prefix
            self._blocks.release(slot)
            req.prefix_hit_tokens, req.prefill_tokens = 0, n  # cache unused
        chunked = bool(chunk and n > chunk)
        # cheap pre-check before running the model (the padded length is at most
        # one bucket/chunk above n, so needed here is exact)
        s_pad = (-(-n // chunk) * chunk if chunked
                 else next(b for b in self.config.buckets() if b >= n))
        needed = self._blocks.blocks_needed(max(n + 1, s_pad))
        if needed > self._blocks.max_fit(slot):
            self._fail_request(req, n)
            return None
        if not self._blocks.can_allocate_for(slot, needed):
            self._waiting.put(req)  # stays pending; retried next cycle
            return None
        k, v, last_logits = self._prefill_kv_tensors(prompt)
        ok = self._install_paged(req, slot, k, v, n)
        if ok is not True:
            if ok is False:
                self._waiting.put(req)
            return None
        # publish this prompt's full blocks for future prefix hits (chunked
        # long prompts seed the cache for their shorter siblings too) — unless
        # the pay-or-skip gate says a hit of this size could never pay, in
        # which case hashing the blocks is wasted host work: a longer future
        # prompt can share at most max_hit tokens with this one
        if max_hit >= min_hit:
            self._blocks.register_blocks(slot, prompt,
                                         self._blocks.owned_for(slot),
                                         skip_blocks=0)
        return self._sample_one(last_logits, req.params)

    def _prefix_min_hit_tokens(self) -> int:
        """Cached-token floor below which a prefix hit is skipped. Fixed by
        RAY_TPU_LLM_PREFIX_MIN_HIT_TOKENS when set; otherwise auto — the hit
        must save at least one dispatch round trip's worth of prefill compute
        (hit_tokens * per_token_prefill >= host_rt). Unmeasured timings keep
        the cache on (the safe direction while EWMAs settle)."""
        from ray_tpu.config import CONFIG as _CFG

        fixed = _CFG.llm_prefix_min_hit_tokens
        if fixed > 0:
            return fixed
        rt, per_tok = self._host_rt_s, self._prefill_per_tok_s
        if rt <= 0 or per_tok <= 0:
            return 0
        return int(rt / per_tok)

    def _prefill_with_prefix(self, req: _Request, slot: int, prompt: List[int],
                             cached_ids: List[int]) -> Optional[int]:
        cfg, c = self.model_config, self.config
        n = len(prompt)
        cached_tokens = len(cached_ids) * c.kv_block_size
        suffix = prompt[cached_tokens:]
        s_pad = next(b for b in c.buckets() if b >= len(suffix))
        needed_new = self._blocks.blocks_needed(
            max(n + 1 - cached_tokens, s_pad))
        total_blocks = len(cached_ids) + needed_new
        if total_blocks > self._blocks.max_fit(slot):
            self._blocks.release(slot)  # undo the attached prefix refs
            self._fail_request(req, n)
            return None
        if not self._blocks.can_allocate_for(slot, needed_new):
            self._blocks.release(slot)
            self._waiting.put(req)
            return None
        tokens = np.zeros((1, s_pad), np.int32)
        tokens[0, : len(suffix)] = suffix
        # fused gather+suffix: ONE device dispatch (the split version paid an
        # extra host->device round trip per warm request)
        k_suf, v_suf, last_logits = self._pops.prefill_suffix_from_state(
            self.params, self.state, jnp.asarray(cached_ids, jnp.int32),
            jnp.asarray(tokens), jnp.int32(len(suffix)),
            n_blocks=len(cached_ids), slot=slot)
        new_ids = self._blocks.allocate(slot, needed_new)
        pad_blocks = s_pad // c.kv_block_size
        if pad_blocks < needed_new:
            extra = (needed_new - pad_blocks) * c.kv_block_size
            k_suf = jnp.pad(k_suf, ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
            v_suf = jnp.pad(v_suf, ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
        row = np.zeros((self._blocks.max_blocks,), np.int32)
        row[: total_blocks] = cached_ids + new_ids
        self.state = self._pops.install_with_prefix(
            self.state, k_suf, v_suf, jnp.asarray(new_ids, jnp.int32),
            jnp.asarray(row), jnp.int32(n), jnp.int32(slot), n_new=needed_new)
        self._blocks.register_blocks(slot, prompt, cached_ids + new_ids,
                                     skip_blocks=len(cached_ids))
        self._blocks.add_hit_tokens(slot, cached_tokens)  # counted only on success
        return self._sample_one(last_logits, req.params)

    def _admit_paged_kv(self, req: _Request, slot: int, k, v) -> bool:
        """Install P/D-transferred KV into blocks; False = not admitted."""
        ok = self._install_paged(req, slot, k, v, len(req.prompt_ids))
        if ok is False:
            self._waiting.put(req)  # prefill_kv still set; stays pending
        return ok is True

    def _grow_or_preempt(self, headroom: int = 1, steps=None) -> None:
        """Before a decode step: every active slot whose next write crosses into
        an unallocated block gets one; when the pool is dry, preempt the
        YOUNGEST request in the SAME pool partition (recompute preemption:
        blocks freed, request re-queued and later re-prefilled from its token
        history; with dp>1 only the slot's own replica pool can relieve it).
        headroom > 1 reserves room for a fused K-step burst, whose block
        tables are frozen; `steps` (the per-slot burst budget from
        _burst_plan) caps each slot's reservation at the steps it will
        actually take, so a near-finished request doesn't grab K blocks."""
        for slot in list(self._active):
            req = self._active[slot]
            if req is None:
                continue
            # host mirror of state.lengths (== prompt + generated - 1, the next
            # write position): saves a device fetch per decode step
            next_write = len(req.prompt_ids) + req.generated - 1
            # graftlint: allow[host-sync-in-hot-path] steps is the host-side burst plan (numpy), not a device array
            slot_headroom = (min(headroom, int(steps[slot]))
                             if steps is not None else headroom)
            # re-check liveness each round: an earlier iteration (or this one)
            # may have preempted this very request — growing a preempted slot
            # would leak blocks into it and corrupt a later occupant's table
            # clamp at the table width: demanding capacity past max_model_len
            # would leak blocks (append index off the table) or preempt
            # innocents forever once the slot is already at full width
            target = min(next_write + slot_headroom, self.config.max_model_len)
            while (self._active[slot] is req
                   and target - 1 >= self._blocks.slot_capacity(slot)):
                if self._blocks.num_free_for(slot) > 0:
                    (bid,) = self._blocks.allocate(slot, 1)
                    index = self._blocks.slot_capacity(slot) // self.config.kv_block_size - 1
                    self.state = self._pops.append_block(
                        self.state, jnp.int32(slot), jnp.int32(index), jnp.int32(bid))
                    continue
                victim = max(
                    (r for r in self._active.values()
                     if r is not None and self._blocks.same_pool(r.slot, slot)),
                    key=lambda r: r.admitted_at)
                self._preempt(victim)
                if victim is req:
                    break  # this slot's request was the victim; nothing to grow

    def _preempt(self, req: _Request) -> None:
        self.num_preemptions += 1
        slot = req.slot
        self._blocks.release(slot)
        self._active[slot] = None
        req.slot = -1
        with self._lock:
            self.num_active -= 1
            self.num_pending += 1
        self._waiting.put(req)

    def _emit(self, req: _Request, tok: int) -> None:
        req.generated += 1
        req.token_history.append(tok)
        if req.first_token_perf_ns == 0:
            self._record_first_token(req)
        with self._lock:
            # _emit_prefill_first_token bumps this from the request thread
            self.total_generated += 1
        stops = req.params.stop_token_ids or [self.tokenizer.eos_token_id]
        finished, reason = False, None
        if tok in stops:
            finished, reason = True, "stop"
        elif req.generated >= req.params.max_tokens:
            finished, reason = True, "length"
        emit_ids = [] if reason == "stop" else [tok]
        text = self.tokenizer.decode(emit_ids) if emit_ids else ""
        req.out_queue.put(RequestOutput(
            request_id=req.id, token_ids=emit_ids, text=text, finished=finished,
            finish_reason=reason, num_prompt_tokens=len(req.prompt_ids),
            num_generated_tokens=req.generated,
        ))
        if finished:
            self._release(req)

    def _release(self, req: _Request) -> None:
        self._record_finish(req)
        if req.slot >= 0:
            if self.config.kv_layout == "paged":
                self._blocks.release(req.slot)
            self._active[req.slot] = None
            req.slot = -1
            with self._lock:
                self.num_active -= 1
                self._requests.pop(req.id, None)
                self._aborted.discard(req.id)

    def _propose_ngram(self, req: "_Request", k: int) -> List[int]:
        """Prompt-lookup drafts (reference vLLM ngram speculator): find the
        most recent earlier occurrence of the trailing n-gram (longest n
        first) and propose the tokens that followed it."""
        ctx = req.token_history  # prompt + every generated token
        if len(ctx) < 2:
            return []
        # graftlint: allow[host-sync-in-hot-path] ngram proposal runs on the host token history (python lists)
        arr = np.asarray(ctx, dtype=np.int32)
        total = len(arr)
        for n in range(min(self.config.ngram_prompt_lookup_max, total - 1), 0, -1):
            tail = arr[-n:]
            # vectorized shifted-equality scan (O(n*len) numpy, not Python
            # slicing per position — at 32k context this must not outweigh
            # the verify step itself); exclude the tail's own occurrence
            m = np.ones(total - n, dtype=bool)
            for j in range(n):
                m &= arr[j:total - n + j] == tail[j]
            hits = np.flatnonzero(m)
            if hits.size:
                # graftlint: allow[host-sync-in-hot-path] hits is a host numpy array from np.where
                start = int(hits[-1])
                cont = ctx[start + n:start + n + k]
                if cont:
                    return cont
        return []

    def _spec_burst_width(self) -> int:
        """Fused-spec burst cap: each window may emit up to k+1 tokens, so the
        per-slot room/budget math divides by the window length. Spec windows
        accept a variable token count, so (unlike plain fused decode) the
        burst width stays capped by the tightest slot."""
        c = self.config
        m = self.decode_steps_target()
        if m == 1:
            return 1
        wlen = c.num_speculative_tokens + 1
        for req in self._active.values():
            if req is None:
                continue
            next_write = len(req.prompt_ids) + req.generated - 1
            kv_room = (c.max_model_len - 1) - next_write
            budget = req.params.max_tokens - req.generated
            m = min(m, max(1, min(kv_room, budget) // wlen))
        return _pow2_floor(m)

    @hot_path
    def _step_decode_spec_fused(self, m: int) -> None:
        """m speculative windows fused per host sync (spec + multi-step
        composed): the n-gram proposal runs ON DEVICE against a per-slot
        history buffer, so successive windows chain without host round trips
        (model_runner.spec_multi)."""
        cfg = self.model_config
        c = self.config
        k = c.num_speculative_tokens
        n = c.max_num_seqs
        active_mask = np.array([r is not None for r in self._active.values()], bool)
        if not active_mask.any():
            return
        self._clock.enter(_DISPATCH)
        # history width bucketed to a power of two: bounds both the H2D upload
        # (not max_model_len when contexts are short) and the spec_multi trace
        # count (one program per width bucket)
        max_ctx = max(len(r.token_history) for r in self._active.values()
                      if r is not None)
        width = min(c.max_model_len,
                    1 << (max_ctx + m * (k + 1) - 1).bit_length())
        hist = np.zeros((n, width), np.int32)
        hlen = np.zeros((n,), np.int32)
        for slot, req in self._active.items():
            if req is None:
                continue
            ctx = req.token_history
            hist[slot, :len(ctx)] = ctx
            hlen[slot] = len(ctx)
        rngs = jnp.stack([self._next_rng() for _ in range(m)])
        t0_perf = time.perf_counter_ns()
        if c.kv_layout == "paged":
            self.state, toks_m, acc_m, drafted_m = self._pops.spec_multi(
                self.params, self.state, jnp.asarray(hist), jnp.asarray(hlen),
                jnp.asarray(active_mask), rngs,
                jnp.asarray(self._temp), jnp.asarray(self._top_p),
                jnp.asarray(self._top_k), m, k, c.ngram_prompt_lookup_max)
        else:
            self.state, toks_m, acc_m, drafted_m = model_runner.spec_multi(
                self.params, self.state, jnp.asarray(hist), jnp.asarray(hlen),
                jnp.asarray(active_mask), cfg, rngs,
                jnp.asarray(self._temp), jnp.asarray(self._top_p),
                jnp.asarray(self._top_k), m, k, c.ngram_prompt_lookup_max)
        self._clock.enter(_FETCH)
        # graftlint: allow[host-sync-in-hot-path] the ONE designed fetch per fused spec window (PR 12 contract)
        toks_m, acc_m, drafted_m = jax.device_get((toks_m, acc_m, drafted_m))
        dur_ns = time.perf_counter_ns() - t0_perf
        # keep the auto-K probe live in fused-spec mode too (per-WINDOW cost,
        # the unit decode_steps_target counts here): without this the EWMA
        # would freeze at whatever the single-window phase measured
        self._note_burst_device_wall(m, dur_ns / 1e9)
        self._clock.enter(_EMIT)
        before = self.total_generated
        burst_reqs = {s: r for s, r in self._active.items() if r is not None}
        advanced = 0
        for step in range(m):
            for slot, req in burst_reqs.items():
                advanced += self._emit_spec_window(
                    # graftlint: allow[host-sync-in-hot-path] acc_m/toks_m already fetched by this window's device_get
                    slot, req, toks_m[step, slot], int(acc_m[step, slot]),
                    # graftlint: allow[host-sync-in-hot-path] drafted_m already fetched by this window's device_get
                    int(drafted_m[step, slot]))
        # a verify window is this path's device step
        self._counters["decode_steps_total"] += m
        self._counters["decode_slot_steps_total"] += advanced
        self._record_burst(self.total_generated - before, dur_ns)

    def _emit_spec_window(self, slot: int, req: "_Request", toks_row,
                          acc: int, drafted: int) -> bool:
        """Emit one verify window's accepted prefix + bonus token for a slot
        (shared by the per-window and fused spec paths): counts acceptance,
        discards tokens past a mid-burst finish, force-finishes at the KV cap.
        True when the slot advanced (it put out at least one token)."""
        if self._active.get(slot) is not req:
            return False  # finished (or aborted) earlier in this burst: discard tail
        if self._aborted and self._finish_abort(req):
            return False  # cancelled mid-burst: tail discarded, blocks freed now
        c = self.config
        self.num_spec_drafted += drafted
        self.num_spec_accepted += min(acc, drafted)
        for t in range(acc + 1):
            if self._active.get(slot) is not req:
                break
            # graftlint: allow[host-sync-in-hot-path] toks_row is the already-fetched numpy burst row
            tok = int(toks_row[t])
            self._last_tokens[slot] = tok
            self._emit(req, tok)
            r2 = self._active.get(slot)
            if r2 is not None and (len(r2.prompt_ids) + r2.generated - 1
                                   >= c.max_model_len - 1):
                r2.out_queue.put(RequestOutput(
                    request_id=r2.id, token_ids=[], finished=True,
                    finish_reason="length",
                    num_prompt_tokens=len(r2.prompt_ids),
                    num_generated_tokens=r2.generated,
                ))
                self._release(r2)
        return True

    @hot_path
    def _step_decode_spec(self) -> None:
        """Speculative decode step: host proposes drafts by n-gram lookup, ONE
        verify forward scores the whole window, accepted prefix + bonus token
        all emit this step (greedy slots only; others ride along with k=0)."""
        cfg = self.model_config
        c = self.config
        self._clock.enter(_GROW)
        if self.decode_steps_target() > 1:
            # pp engines never reach here with >1 (start() downgrades the
            # target): pp keeps per-step scheduling (microbatch ticks)
            m = self._spec_burst_width()
            if m > 1 and c.kv_layout == "paged":
                # every window position of the burst must land in an owned block
                self._grow_or_preempt(headroom=m * (c.num_speculative_tokens + 1))
                m = min(m, self._spec_burst_width())  # preemption changed the set
            if m > 1:
                self._step_decode_spec_fused(m)
                return
        k = c.num_speculative_tokens
        wlen = k + 1
        if c.kv_layout == "paged":
            # every window position must land in an owned block
            self._grow_or_preempt(headroom=wlen)
        n = c.max_num_seqs
        self._clock.enter(_DISPATCH)
        window = np.zeros((n, wlen), np.int32)
        draft_len = np.zeros((n,), np.int32)
        active_mask = np.zeros((n,), bool)
        for slot, req in self._active.items():
            if req is None:
                continue
            active_mask[slot] = True
            window[slot, 0] = self._last_tokens[slot]
            if self._temp[slot] > 0:
                continue  # greedy-accept is exact only at temperature 0
            next_write = len(req.prompt_ids) + req.generated - 1
            room = (c.max_model_len - 1) - next_write - 1
            budget = req.params.max_tokens - req.generated - 1
            cap = max(0, min(k, room, budget))
            drafts = self._propose_ngram(req, cap) if cap else []
            draft_len[slot] = len(drafts)
            if drafts:
                window[slot, 1:1 + len(drafts)] = drafts
        if not active_mask.any():
            return  # pool-exhaustion preemption may have drained every slot
        t0_perf = time.perf_counter_ns()
        if c.kv_layout == "paged":
            self.state, out_toks, n_acc = self._pops.spec_verify(
                self.params, self.state, jnp.asarray(window),
                jnp.asarray(draft_len), jnp.asarray(active_mask),
                self._next_rng(), jnp.asarray(self._temp),
                jnp.asarray(self._top_p), jnp.asarray(self._top_k))
        elif c.pipeline_parallel_size > 1:
            self.state, out_toks, n_acc = self._spec_pp_jit(
                self.params, self.state, jnp.asarray(window),
                jnp.asarray(draft_len), jnp.asarray(active_mask),
                self._next_rng(), jnp.asarray(self._temp),
                jnp.asarray(self._top_p), jnp.asarray(self._top_k))
        else:
            self.state, out_toks, n_acc = model_runner.spec_verify_step(
                self.params, self.state, jnp.asarray(window),
                jnp.asarray(draft_len), jnp.asarray(active_mask), cfg,
                self._next_rng(), jnp.asarray(self._temp),
                jnp.asarray(self._top_p), jnp.asarray(self._top_k))
        self._clock.enter(_FETCH)
        # graftlint: allow[host-sync-in-hot-path] the ONE designed fetch per spec-decode step
        out_toks, n_acc = jax.device_get((out_toks, n_acc))
        dur_ns = time.perf_counter_ns() - t0_perf
        # the verify forward is close enough to a decode step to feed the
        # auto-K probe: once the EWMA settles, single-window spec engines in
        # auto mode graduate to fused multi-window bursts
        self._note_burst_device_wall(1, dur_ns / 1e9)
        self._clock.enter(_EMIT)
        before = self.total_generated
        burst_reqs = {s: r for s, r in self._active.items() if r is not None}
        advanced = 0
        for slot, req in burst_reqs.items():
            advanced += self._emit_spec_window(
                slot, req, out_toks[slot],
                # graftlint: allow[host-sync-in-hot-path] n_acc/draft_len already fetched by this step's device_get
                int(n_acc[slot]), int(draft_len[slot]))
        self._counters["decode_steps_total"] += 1
        self._counters["decode_slot_steps_total"] += advanced
        self._record_burst(self.total_generated - before, dur_ns)

    @hot_path
    def _step_decode(self) -> None:
        cfg = self.model_config
        if self.config.num_speculative_tokens:
            self._step_decode_spec()
            return
        self._clock.enter(_GROW)
        k_steps, steps = self._burst_plan()
        if self.config.kv_layout == "paged":
            self._grow_or_preempt(headroom=k_steps, steps=steps)
            k_steps, steps = self._burst_plan()  # preemption changed the set
        active_mask = np.array([r is not None for r in self._active.values()], bool)
        if not active_mask.any():
            return  # preemption may have drained every slot this cycle
        t0_perf = time.perf_counter_ns()
        self._clock.enter(_DISPATCH)
        if k_steps > 1:
            # fused burst: K decode+sample iterations, ONE host sync (vLLM
            # multi-step scheduling). The
            # per-slot steps budget rides along, so a request one token from
            # its max_tokens no longer caps the whole batch at K=1 — it stops
            # advancing on device and retires at the burst boundary while the
            # rest of the batch runs full-width.
            rngs = jnp.stack([self._next_rng() for _ in range(k_steps)])
            steps_dev = jnp.asarray(steps)
            if self.config.kv_layout == "paged":
                self.state, toks_dev = self._pops.decode_multi(
                    self.params, self.state, jnp.asarray(self._last_tokens),
                    jnp.asarray(active_mask), rngs,
                    jnp.asarray(self._temp), jnp.asarray(self._top_p),
                    jnp.asarray(self._top_k), steps_dev)
            else:
                self.state, toks_dev = model_runner.decode_multi(
                    self.params, self.state, jnp.asarray(self._last_tokens),
                    jnp.asarray(active_mask), cfg, rngs,
                    jnp.asarray(self._temp), jnp.asarray(self._top_p),
                    jnp.asarray(self._top_k), steps_dev)
        else:
            if self.config.kv_layout == "paged":
                self.state, logits = self._pops.decode_step(
                    self.params, self.state, jnp.asarray(self._last_tokens),
                    jnp.asarray(active_mask),
                )
            elif self.config.pipeline_parallel_size > 1:
                self.state, logits = self._decode_pp_jit(
                    self.params, self.state, jnp.asarray(self._last_tokens),
                    jnp.asarray(active_mask),
                )
            else:
                self.state, logits = model_runner.decode_step(
                    self.params, self.state, jnp.asarray(self._last_tokens),
                    jnp.asarray(active_mask), cfg,
                )
            toks_dev = model_runner.sample_tokens(
                self._next_rng(), logits, jnp.asarray(self._temp),
                jnp.asarray(self._top_p), jnp.asarray(self._top_k))
        self._clock.enter(_FETCH)
        # graftlint: allow[host-sync-in-hot-path] the ONE designed host sync per K-step fused burst (PR 12); at K=1 the per-step token fetch
        toks_burst = np.asarray(toks_dev)  # [K, slots] — the only fetch
        if k_steps == 1:
            toks_burst = toks_burst[None, :]
        dur_ns = time.perf_counter_ns() - t0_perf
        self._note_burst_device_wall(k_steps, dur_ns / 1e9)
        self._clock.enter(_EMIT)
        burst_reqs = {slot: req for slot, req in self._active.items() if req is not None}
        emitted = 0
        for t in range(toks_burst.shape[0]):
            for slot, req in burst_reqs.items():
                if t >= steps[slot]:
                    continue  # this slot's own budget ended before the burst
                if self._active.get(slot) is not req:
                    continue  # finished (or aborted) earlier in this burst
                if self._aborted and self._finish_abort(req):
                    continue  # cancelled mid-burst: tail discarded, blocks freed
                # graftlint: allow[host-sync-in-hot-path] toks_burst is the already-fetched numpy burst
                tok = int(toks_burst[t, slot])
                self._last_tokens[slot] = tok
                self._emit(req, tok)
                emitted += 1
                r2 = self._active[slot]
                # host mirror of state.lengths: the last sampled token is not yet
                # written to KV, so device lengths == prompt + generated - 1.
                # Mirroring avoids a SECOND device round trip per decode step.
                if r2 is not None and (len(r2.prompt_ids) + r2.generated - 1
                                       >= self.config.max_model_len - 1):
                    r2.out_queue.put(RequestOutput(
                        request_id=r2.id, token_ids=[], finished=True,
                        finish_reason="length",
                        num_prompt_tokens=len(r2.prompt_ids),
                        num_generated_tokens=r2.generated,
                    ))
                    self._release(r2)
        # device steps of the burst, and the slot-steps among them that
        # put out a token: their ratio over max_num_seqs is the occupancy
        self._counters["decode_steps_total"] += k_steps
        self._counters["decode_slot_steps_total"] += emitted
        self._record_burst(emitted, dur_ns)

    def _record_burst(self, emitted: int, dur_ns: int) -> None:
        """Per-burst decode telemetry: ONE observation per K-step burst
        instead of per host step, so the windowed quantiles stay truthful
        under fused mode (the burst's interval itself is the llm.loop.dispatch
        and llm.loop.fetch spans). Guarded like _export_metrics — metrics
        must never take the engine down."""
        try:
            tags = self._model_tag()
            if emitted:
                telemetry.get_counter(
                    "llm_generated_tokens_total",
                    "tokens emitted by the engine (all requests)",
                    # graftlint: allow[host-sync-in-hot-path] emitted is a python int; metric emission is host-side
                    tag_keys=("model",)).inc(float(emitted), tags=tags)
                if dur_ns > 0:
                    telemetry.get_histogram(
                        "llm_burst_tokens_per_s",
                        "engine decode throughput per fused burst",
                        tag_keys=("model",),
                        boundaries=[10, 50, 100, 250, 500, 1000, 2500, 5000,
                                    10000, 25000]).observe(
                        emitted / (dur_ns / 1e9), tags=tags)
        except Exception as e:
            _metrics_guard_warn("_record_burst", e)

    @hot_path
    def _loop(self) -> None:
        import time as _time

        next_metrics_push = 0.0
        while not self._shutdown:
            try:
                # liveness heartbeat for LLMServer.check_health: a wedged
                # device call shows up as a stale tick while requests wait
                self._last_tick_monotonic = _time.monotonic()
                self._clock.enter(_ADMIT)
                self._admit()
                self._process_aborts()
                # periodic gauge refresh: /metrics must serve current llm_*
                # values even when nothing polls engine.metrics() (ADVICE r3)
                now = _time.monotonic()
                if now >= next_metrics_push:
                    next_metrics_push = now + 5.0
                    self.metrics()
                if any(r is not None for r in self._active.values()):
                    self._step_decode()
                else:
                    from ray_tpu.config import CONFIG as _CFG

                    self._clock.enter(_IDLE)
                    self._wakeup.wait(timeout=_CFG.llm_engine_idle_wait_s)
                    self._wakeup.clear()
            except Exception:
                import traceback

                traceback.print_exc()
                # fail all in-flight requests rather than hanging clients —
                # including one caught mid-admission (in neither _waiting nor
                # _active), whose client would otherwise block forever
                if self._admitting is not None:
                    self._admitting.out_queue.put(RequestOutput(
                        request_id=self._admitting.id, token_ids=[], finished=True,
                        finish_reason="error"))
                    with self._lock:
                        self.num_pending -= 1  # it left _waiting but never admitted
                        self._requests.pop(self._admitting.id, None)
                        self._aborted.discard(self._admitting.id)
                    self._admitting = None
                for slot, req in list(self._active.items()):
                    if req is not None:
                        req.out_queue.put(RequestOutput(
                            request_id=req.id, token_ids=[], finished=True,
                            finish_reason="error"))
                        self._release(req)
                while True:
                    try:
                        req = self._waiting.get_nowait()
                    except queue.Empty:
                        break
                    self._fail_request(req, len(req.prompt_ids), "error")
                time.sleep(0.1)
        self._clock.enter(None)


_INIT_CACHE: Dict[Tuple[Any, str, Any], Any] = {}
_PROM_GAUGES: Dict[str, Any] = {}  # engine metric name -> shared Gauge


def llama_init_cached(cfg, dtype: str = "bfloat16", mesh=None):
    """Seeded random params once per (config, dtype, mesh) — the path a preset
    name as `model_source` takes (tests, the chip smoke; real use loads a
    checkpoint). Keyed by the frozen config itself: a depth-cut copy of a
    preset shares its name. Initialised under jit with the cast inside and,
    given a mesh, straight into the engine's shardings: no f32 copy and no
    unsharded copy of the model is left on a device next to the one served."""
    from ray_tpu.models import llama
    from ray_tpu.parallel.sharding import named_sharding

    def init(rng):
        return jax.tree.map(lambda x: x.astype(dtype), llama.init(rng, cfg))

    key = (cfg, str(jnp.dtype(dtype)), mesh)
    if key not in _INIT_CACHE:
        shardings = None
        if mesh is not None:
            rules = model_runner.infer_rules_for_mesh(mesh)
            shardings = jax.tree.map(
                lambda _, axes: named_sharding(mesh, *axes, rules=rules),
                jax.eval_shape(init, jax.random.PRNGKey(0)), llama.param_axes(cfg))
        _INIT_CACHE[key] = jax.jit(init, out_shardings=shardings)(jax.random.PRNGKey(0))
    return _INIT_CACHE[key]
