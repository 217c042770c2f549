"""Central knob registry: every RAY_TPU_* environment knob, in one place.

THE single source of truth for the project's environment knobs (name, type,
default, one-line doc, owning subsystem). `ray_tpu.config` builds its CONFIG
flag table from the entries that carry an `attr` (the operator-facing flags);
entries without one are read directly from the environment at their use site
(module-level tunables like the grad-sync worker knobs) or are `internal=True`
worker-plumbing protocol variables the runtime sets for its own children
(RAY_TPU_ARENA, RAY_TPU_TRAIN_RANK, ...).

Invariants, machine-checked by graftlint (`ray-tpu lint`, check knob-registry):

- every `RAY_TPU_*` string the codebase reads from the environment is
  registered here (unregistered reads are lint violations);
- every non-internal entry is still referenced somewhere (stale entries are
  lint violations);
- the README knob tables are GENERATED from this registry
  (`ray-tpu lint --write-docs`); hand-edits between the markers are drift and
  fail lint.

This module must stay stdlib-only: graftlint loads it while analyzing the
tree, and the analyzer guarantees it never pulls in jax or the runtime.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class Knob:
    env: str  # environment variable name
    type: str  # "int" | "float" | "bool" | "str"
    default: Any  # None = unset/auto
    doc: str  # one-line operator-facing description
    subsystem: str  # owning subsystem (one README table per subsystem)
    attr: Optional[str] = None  # ray_tpu.config.CONFIG attribute, if any
    internal: bool = False  # worker-plumbing protocol, not an operator flag


KNOBS: List[Knob] = [
    # -- core
    Knob("RAY_TPU_NUM_CPUS", "float", None,
         "CPU capacity this node advertises (default: os.cpu_count()).",
         "core", attr="num_cpus"),
    Knob("RAY_TPU_NUM_TPUS", "float", None,
         "TPU chip capacity this node advertises (default: auto-detect).",
         "core", attr="num_tpus"),
    Knob("RAY_TPU_MAX_WORKERS_PER_NODE", "int", 16,
         "Worker-process cap per node (reference: raylet worker pool size).",
         "core", attr="max_workers_per_node"),
    Knob("RAY_TPU_TASK_MAX_RETRIES", "int", 3,
         "Default max_retries for @remote tasks when unspecified "
         "(reference task_max_retries / TASK_MAX_RETRIES default).",
         "core", attr="task_max_retries"),
    Knob("RAY_TPU_ACTOR_MAX_RESTARTS", "int", 0,
         "Default max_restarts for actors when unspecified (reference "
         "actor restart semantics: 0 = never restart).",
         "core", attr="actor_max_restarts"),
    Knob("RAY_TPU_WORKER_START_TIMEOUT_S", "float", 60.0,
         "How long the pool waits for a spawned worker's handshake "
         "(reference worker_register_timeout_seconds).",
         "core", attr="worker_start_timeout_s"),
    # -- object-store
    Knob("RAY_TPU_OBJECT_STORE_BYTES", "int", 512 * 1024 * 1024,
         "Shared-memory arena capacity per node (plasma-equivalent).",
         "object-store", attr="object_store_bytes"),
    Knob("RAY_TPU_SPILL_DIR", "str", "/tmp",
         "Directory for objects spilled from shared memory to disk.",
         "object-store", attr="spill_dir"),
    Knob("RAY_TPU_SPILL_THRESHOLD", "float", 0.8,
         "Arena-usage fraction above which LRU spilling starts.",
         "object-store", attr="spill_threshold"),
    Knob("RAY_TPU_SPILL_TARGET", "float", 0.5,
         "Arena-usage fraction spilling drives down to.",
         "object-store", attr="spill_target"),
    Knob("RAY_TPU_MEMORY_USAGE_THRESHOLD", "float", 0.95,
         "System-memory fraction that triggers the OOM worker killer "
         "(reference memory_monitor.h).",
         "object-store", attr="memory_usage_threshold"),
    Knob("RAY_TPU_MEMORY_MONITOR_REFRESH_MS", "int", 250,
         "Memory monitor / spill check period.",
         "object-store", attr="memory_monitor_refresh_ms"),
    Knob("RAY_TPU_INLINE_THRESHOLD_BYTES", "int", 100 * 1024,
         "Objects below this travel inline in control messages instead of the "
         "arena (reference max_direct_call_object_size).",
         "object-store", attr="inline_threshold_bytes"),
    Knob("RAY_TPU_OOB_THRESHOLD_BYTES", "int", 1 << 16,
         "Pickle buffers at or above this serialize out-of-band (zero-copy "
         "into the arena) instead of inline in the pickle stream.",
         "object-store", attr="oob_threshold_bytes"),
    Knob("RAY_TPU_OBJECT_LOCATION_TIMEOUT_S", "float", 60.0,
         "How long a get() waits for a recovering object's new location "
         "after lineage resubmission before failing.",
         "object-store", attr="object_location_timeout_s"),
    Knob("RAY_TPU_LOCALIZE_PULL_TIMEOUT_S", "float", 120.0,
         "Deadline for pulling a task's missing arguments to its assigned "
         "node; expiry triggers lineage reconstruction or task failure.",
         "object-store", attr="localize_pull_timeout_s"),
    # -- transfer
    Knob("RAY_TPU_TRANSFER_CHUNK_BYTES", "int", 4 * 1024 * 1024,
         "Chunk size for direct node-to-node object transfers "
         "(reference push_manager.h chunked push).",
         "transfer", attr="transfer_chunk_bytes"),
    Knob("RAY_TPU_TRANSFER_INFLIGHT_BYTES", "int", 256 * 1024 * 1024,
         "Per-node byte budget for concurrent incoming object pulls "
         "(reference pull_manager.h admission control).",
         "transfer", attr="transfer_inflight_bytes"),
    Knob("RAY_TPU_TRANSFER_MAX_PULLS", "int", 8,
         "Max concurrent pulls a node issues (and streams it serves).",
         "transfer", attr="transfer_max_pulls"),
    Knob("RAY_TPU_TRANSFER_UDS", "bool", True,
         "Same-host data-plane pulls ride an abstract unix socket instead of "
         "loopback TCP (~1.4x bulk throughput); remote pulls and TLS mode "
         "always use TCP. The authkey challenge gates both transports.",
         "transfer", attr="transfer_uds"),
    Knob("RAY_TPU_TRANSFER_STRIPE_THRESHOLD_BYTES", "int", 8 * 1024 * 1024,
         "Objects at or above this size pull as concurrent byte-range stripes "
         "over pooled connections (0 disables striping). All stripes of one "
         "pull share a single admission grant.",
         "transfer", attr="transfer_stripe_threshold_bytes"),
    Knob("RAY_TPU_TRANSFER_STRIPES", "int", 4,
         "Max concurrent range streams per striped pull.",
         "transfer", attr="transfer_stripes"),
    Knob("RAY_TPU_TRANSFER_STRIPE_MIN_BYTES", "int", 2 * 1024 * 1024,
         "Never split a pull so finely that a stripe falls below this many "
         "bytes (each stripe pays a request/admission handshake).",
         "transfer", attr="transfer_stripe_min_bytes"),
    Knob("RAY_TPU_TRANSFER_SAME_HOST_MAP", "bool", True,
         "When the source's shm/arena/spill location is directly readable "
         "from the pulling process (source shares this machine's /dev/shm — "
         "colocated node processes), map it in place instead of copying the "
         "bytes over loopback TCP (reference: one plasma store per node). "
         "The striped wire path is for genuinely-remote peers.",
         "transfer", attr="transfer_same_host_map"),
    Knob("RAY_TPU_TRANSFER_TIMEOUT_S", "float", 300.0,
         "Deadline for one direct object transfer before head-relay fallback.",
         "transfer", attr="transfer_timeout_s"),
    Knob("RAY_TPU_TRANSFER_STALL_TIMEOUT_S", "float", 60.0,
         "Per-socket-op stall bound on data-plane transfers (a half-dead peer "
         "must not pin admission slots / puller threads forever).",
         "transfer", attr="transfer_stall_timeout_s"),
    # -- device-plane
    Knob("RAY_TPU_DEVICE_PLANE", "bool", True,
         "Enable the PJRT transfer-server plane: jax.Arrays move between actor "
         "processes device-to-device (DCN/ICI on pods) instead of "
         "device->host->pickle (reference gpu_object_manager + NCCL channels).",
         "device-plane", attr="device_plane"),
    Knob("RAY_TPU_DEVICE_OBJECTS", "str", "fetch",
         "jax.Arrays in the object store: 'off' = host copy only; 'fetch' "
         "(default) = host copy kept, consumers pull device-to-device when "
         "possible; 'native' = stub only, device-resident at the producer "
         "(reference gpu_object_manager semantics: loss -> reconstruction).",
         "device-plane", attr="device_objects"),
    Knob("RAY_TPU_DEVICE_OBJECT_MIN_BYTES", "int", 1 << 20,
         "Device arrays below this size skip the transfer plane (control-message "
         "inlining beats an arm round-trip for small tensors).",
         "device-plane", attr="device_object_min_bytes"),
    # -- collective
    Knob("RAY_TPU_COLLECTIVE_OP_TIMEOUT_S", "float", 30.0,
         "Host-plane collective op timeout (allreduce/broadcast/...); "
         "barriers wait 2x this.",
         "collective", attr="collective_op_timeout_s"),
    Knob("RAY_TPU_COLLECTIVE_ABORT_POLL_INTERVAL_S", "float", 0.25,
         "How often ring-path collective waits (stream reduce, gathers, tree "
         "relays) probe the group coordinator's abort poison flag: a dead "
         "rank costs survivors one interval, not collective_op_timeout_s.",
         "collective", attr="collective_abort_poll_interval_s"),
    Knob("RAY_TPU_COLLECTIVE_RING_THRESHOLD_BYTES", "int", 64 * 1024,
         "SHM-collective payloads at or above this size move peer-to-peer over "
         "the data plane (ring path, coordinator carries metadata only); "
         "smaller payloads ride the coordinator board directly.",
         "collective", attr="collective_ring_threshold_bytes"),
    Knob("RAY_TPU_COLLECTIVE_SERVER_STREAMS", "int", 64,
         "Concurrent serve streams on a rank's collective data-plane server. "
         "Ring reads block until the local chunk is published, so this is "
         "sized above transfer_max_pulls to keep blocked readers from "
         "starving live ones.",
         "collective", attr="collective_server_streams"),
    # -- control-plane
    Knob("RAY_TPU_AGENT_HEARTBEAT_S", "float", 2.0,
         "Node-agent heartbeat period to the head.",
         "control-plane", attr="agent_heartbeat_s"),
    Knob("RAY_TPU_AGENT_BATCH_MAX", "int", 128,
         "Max frames coalesced into one gRPC agent-stream message (batching "
         "packs only already-queued frames: zero added latency).",
         "control-plane", attr="agent_batch_max"),
    Knob("RAY_TPU_AGENT_QUEUE_DEPTH", "int", 4096,
         "Outbound frame buffer per agent stream; a stalled peer exerts "
         "backpressure once full instead of accumulating frames in RAM.",
         "control-plane", attr="agent_queue_depth"),
    Knob("RAY_TPU_AGENT_SEND_TIMEOUT_S", "float", 30.0,
         "How long send() blocks on a backed-up agent stream before raising.",
         "control-plane", attr="agent_send_timeout_s"),
    Knob("RAY_TPU_AGENT_HEARTBEAT_TIMEOUT_S", "float", 10.0,
         "Head marks an agent dead after this long without a heartbeat "
         "(reference gcs_health_check_manager.h).",
         "control-plane", attr="agent_heartbeat_timeout_s"),
    Knob("RAY_TPU_AGENT_RECONNECT_TIMEOUT_S", "float", 60.0,
         "How long a node agent keeps its workers alive while redialing a "
         "restarted head before giving up (reference: raylets buffering "
         "through a GCS restart, NotifyGCSRestart).",
         "control-plane", attr="agent_reconnect_timeout_s"),
    Knob("RAY_TPU_HEAD_RECONNECT_TIMEOUT_S", "float", 30.0,
         "How long a driver/worker control context redials an unreachable "
         "head (jittered backoff) before failing head-requiring calls with "
         "HeadUnavailableError.",
         "control-plane", attr="head_reconnect_timeout_s"),
    Knob("RAY_TPU_HEAD_RECONNECT_BACKOFF_S", "float", 0.25,
         "Initial redial backoff for a lost head connection; doubles per "
         "attempt with jitter.",
         "control-plane", attr="head_reconnect_backoff_s"),
    Knob("RAY_TPU_HEAD_RECONNECT_BACKOFF_MAX_S", "float", 3.0,
         "Redial backoff ceiling for a lost head connection.",
         "control-plane", attr="head_reconnect_backoff_max_s"),
    Knob("RAY_TPU_HEAD_OUTBOX_LIMIT", "int", 4096,
         "Max loss-intolerant control messages (decref/kill/drop_stream, "
         "agent relay frames) buffered for sequence-numbered replay across a "
         "head outage; beyond it the oldest are dropped with a warning.",
         "control-plane", attr="head_outbox_limit"),
    Knob("RAY_TPU_HEAD_RESTART_GRACE_S", "float", 30.0,
         "Reaper grace window after head boot: agents that were healthy "
         "through a head outage get this long to reattach before the "
         "heartbeat reaper may declare them dead.",
         "control-plane", attr="head_restart_grace_s"),
    Knob("RAY_TPU_SESSION_DIR", "str", "/tmp/ray_tpu_session",
         "Session directory (head metadata, jobs, authkey, usage report).",
         "control-plane", attr="session_dir"),
    Knob("RAY_TPU_CLIENT_AUTHKEY", "str", None,
         "Cluster authkey for remote drivers/agents (default: generated and "
         "persisted in the session dir).",
         "control-plane", attr="client_authkey"),
    Knob("RAY_TPU_GCS_PERSISTENCE_PATH", "str", None,
         "Journal file for GCS KV persistence across restarts (default: off).",
         "control-plane", attr="gcs_persistence_path"),
    Knob("RAY_TPU_GCS_OWNER_CHECK_EVERY", "int", 32,
         "URI-journal split-brain fencing: re-verify lease ownership every N "
         "appends (lower = faster usurper detection, more object reads).",
         "control-plane", attr="gcs_owner_check_every"),
    # -- security
    Knob("RAY_TPU_TLS_HANDSHAKE_TIMEOUT_S", "float", 15.0,
         "Deferred server-side TLS handshake deadline per connection.",
         "security", attr="tls_handshake_timeout_s"),
    Knob("RAY_TPU_USE_TLS", "bool", False,
         "mTLS on the gRPC agent channel and the data/device-plane listeners; "
         "plaintext peers are refused (reference tls_utils.py RAY_USE_TLS).",
         "security", attr="use_tls"),
    Knob("RAY_TPU_TLS_CA", "str", None,
         "CA certificate path (both trust root and client-auth verifier).",
         "security", attr="tls_ca"),
    Knob("RAY_TPU_TLS_CERT", "str", None,
         "Cluster certificate path (`ray-tpu tls-init` mints one).",
         "security", attr="tls_cert"),
    Knob("RAY_TPU_TLS_KEY", "str", None,
         "Cluster private key path.",
         "security", attr="tls_key"),
    Knob("RAY_TPU_SERVE_INGRESS_TLS", "bool", False,
         "Serve the HTTP and gRPC ingress proxies over TLS using the cluster "
         "certificate (server-side TLS: external clients verify against "
         "ca.crt but need no client cert, unlike the inter-node mTLS planes).",
         "security", attr="serve_ingress_tls"),
    # -- runtime-env
    Knob("RAY_TPU_CONTAINER_RUNTIME", "str", None,
         "Container launcher binary for container/image_uri runtime envs "
         "(default: docker, then podman, from PATH). Point it at a recording "
         "stub to test invocations without a real runtime.",
         "runtime-env", attr="container_runtime"),
    # -- job
    Knob("RAY_TPU_JOB_STOP_GRACE_S", "float", 5.0,
         "SIGTERM-to-SIGKILL grace when stopping a submitted job's process "
         "group (reference: job stop_timeout).",
         "job", attr="job_stop_grace_s"),
    # -- dag
    Knob("RAY_TPU_DAG_CHANNEL_BUFFER_BYTES", "int", 4 * 1024 * 1024,
         "Default seqlock shm channel capacity for compiled DAGs "
         "(experimental_compile buffer_size_bytes; reference "
         "ChannelContext buffer sizing).",
         "dag", attr="dag_channel_buffer_bytes"),
    # -- data
    Knob("RAY_TPU_DATA_MAX_INFLIGHT_TASKS_PER_OP", "int", 8,
         "Streaming-executor backpressure: tasks in flight per operator "
         "(reference backpressure_policy concurrency caps).",
         "data", attr="data_max_inflight_tasks_per_op"),
    Knob("RAY_TPU_DATA_ACTOR_POOL_MAX_SIZE", "int", 4,
         "Default actor-pool size for map_batches(Class) stages.",
         "data", attr="data_actor_pool_max_size"),
    Knob("RAY_TPU_DATA_READ_OP_MIN_NUM_BLOCKS", "int", 8,
         "Default read parallelism when the datasource does not dictate one.",
         "data", attr="data_read_op_min_num_blocks"),
    Knob("RAY_TPU_DATA_TARGET_MAX_BLOCK_SIZE", "int", 128 * 1024 * 1024,
         "Blocks above this split on output (reference target_max_block_size).",
         "data", attr="data_target_max_block_size"),
    Knob("RAY_TPU_DATA_TARGET_MIN_BLOCK_SIZE", "int", 1 * 1024 * 1024,
         "Coalesce blocks below this (reference target_min_block_size).",
         "data", attr="data_target_min_block_size"),
    Knob("RAY_TPU_DATA_DEFAULT_BATCH_SIZE", "int", 1024,
         "map_batches/iter_batches batch size when unspecified.",
         "data", attr="data_default_batch_size"),
    Knob("RAY_TPU_DATA_OP_OUTPUT_BUFFER_LIMIT", "int", 16,
         "Streaming-executor per-operator output queue cap (backpressure).",
         "data", attr="data_op_output_buffer_limit"),
    Knob("RAY_TPU_DATA_PUSH_BASED_SHUFFLE", "bool", False,
         "Staged-merge shuffle for large sorts (reference "
         "push_based_shuffle_task_scheduler; RAY_DATA_PUSH_BASED_SHUFFLE).",
         "data", attr="data_push_based_shuffle"),
    Knob("RAY_TPU_DATA_PUSH_SHUFFLE_MERGE_FACTOR", "int", 8,
         "Map-round width for the push-based shuffle (fan-in bound).",
         "data", attr="data_push_shuffle_merge_factor"),
    # -- serve
    Knob("RAY_TPU_SERVE_RECONCILE_INTERVAL_S", "float", 0.2,
         "Serve controller reconciliation loop period (replica "
         "create/kill, health checks, autoscale decisions).",
         "serve", attr="serve_reconcile_interval_s"),
    Knob("RAY_TPU_SERVE_REPLICA_WAIT_S", "float", 30.0,
         "How long a handle call waits for a live replica before failing "
         "(reference handle resolution timeout).",
         "serve", attr="serve_replica_wait_s"),
    Knob("RAY_TPU_SERVE_HEALTH_CHECK_PERIOD_S", "float", 5.0,
         "Default replica health-check period (per-deployment override in "
         "DeploymentConfig; reference health_check_period_s).",
         "serve", attr="serve_health_check_period_s"),
    Knob("RAY_TPU_SERVE_HEALTH_CHECK_TIMEOUT_S", "float", 10.0,
         "Default grace before an unresponsive replica is replaced "
         "(reference health_check_timeout_s).",
         "serve", attr="serve_health_check_timeout_s"),
    Knob("RAY_TPU_SERVE_MAX_ONGOING_REQUESTS", "int", 8,
         "Default per-replica concurrent-request cap "
         "(reference max_ongoing_requests).",
         "serve", attr="serve_max_ongoing_requests"),
    Knob("RAY_TPU_SERVE_MAX_QUEUED_REQUESTS", "int", -1,
         "Default per-deployment queue cap beyond replica capacity "
         "(max_ongoing_requests x replicas): excess handle calls are shed "
         "with BackPressureError / HTTP 503 + Retry-After instead of "
         "queueing into latency collapse. -1 = unbounded (no shedding).",
         "serve", attr="serve_max_queued_requests"),
    Knob("RAY_TPU_SERVE_REQUEST_RETRIES", "int", 3,
         "Max times a handle call is re-sent to a DIFFERENT replica after a "
         "replica-death/unavailable failure (deployments with "
         "retryable=False never retry). User-code exceptions never retry.",
         "serve", attr="serve_request_retries"),
    Knob("RAY_TPU_SERVE_RETRY_BACKOFF_S", "float", 0.05,
         "Base of the jittered exponential backoff between serve request "
         "retries (attempt N sleeps ~base*2^(N-1), capped).",
         "serve", attr="serve_retry_backoff_s"),
    Knob("RAY_TPU_SERVE_RETRY_BACKOFF_MAX_S", "float", 2.0,
         "Cap on the serve request retry backoff.",
         "serve", attr="serve_retry_backoff_max_s"),
    Knob("RAY_TPU_SERVE_SUSPECT_TTL_S", "float", 30.0,
         "How long the handle router excludes a replica after a "
         "replica-death classified failure (the suspect list bridges the gap "
         "until the controller's health check removes it from the long-poll "
         "view).",
         "serve", attr="serve_suspect_ttl_s"),
    Knob("RAY_TPU_SERVE_DRAIN_TIMEOUT_S", "float", 30.0,
         "Default grace a DRAINING replica gets to finish in-flight requests "
         "on scale-down/rolling update/shutdown before it is killed anyway "
         "(per-deployment override: drain_timeout_s).",
         "serve", attr="serve_drain_timeout_s"),
    Knob("RAY_TPU_SERVE_AUTOSCALE_INTERVAL_S", "float", 0.0,
         "Tick period of the head-side serve autoscaling loop "
         "(serve/autoscaler.py). 0 (default) paces on the metrics-history "
         "scraper's frames (one decision pass per scrape), which keeps the "
         "loop and its inputs in lockstep.",
         "serve", attr="serve_autoscale_interval_s"),
    Knob("RAY_TPU_SERVE_AUTOSCALE_BURN_TICKS", "int", 2,
         "Consecutive ticks an SLO burn / queue-over-target signal must "
         "persist before the loop scales a deployment up (the short half of "
         "the hysteresis pair: one noisy scrape never resizes the fleet).",
         "serve", attr="serve_autoscale_burn_ticks"),
    Knob("RAY_TPU_SERVE_AUTOSCALE_CLEAN_TICKS", "int", 3,
         "Consecutive clean ticks (no burning SLO, no queue pressure) "
         "required before a scale-down is considered (the long half of the "
         "hysteresis pair; scale-down additionally needs the down-cooldown "
         "elapsed and no replica still DRAINING).",
         "serve", attr="serve_autoscale_clean_ticks"),
    Knob("RAY_TPU_SERVE_AUTOSCALE_UP_COOLDOWN_S", "float", 3.0,
         "Minimum seconds between successive scale-UPs of one deployment "
         "(lets the previous step's replicas absorb load before adding more).",
         "serve", attr="serve_autoscale_up_cooldown_s"),
    Knob("RAY_TPU_SERVE_AUTOSCALE_DOWN_COOLDOWN_S", "float", 30.0,
         "Minimum seconds after ANY scale change before a scale-down (a "
         "flapping SLO must not thrash the paged-KV pool with drain/start "
         "churn).",
         "serve", attr="serve_autoscale_down_cooldown_s"),
    Knob("RAY_TPU_SERVE_AUTOSCALE_QUEUE_TARGET", "float", 4.0,
         "Default desired in-flight requests per replica for mode=\"slo\" "
         "autoscaling (per-deployment override: "
         "AutoscalingConfig.target_queue_depth). The loop scales toward "
         "ceil(queue_depth / target).",
         "serve", attr="serve_autoscale_queue_target"),
    Knob("RAY_TPU_SERVE_AUTOSCALE_STARTUP_TIMEOUT_S", "float", 30.0,
         "How long a scale-up may sit below target before it is declared "
         "stuck: the deficit is handed to the node autoscaler as a demand "
         "hint, wedged STARTING replicas restart elsewhere, and the handle's "
         "anticipated-capacity admission window expires (shedding resumes).",
         "serve", attr="serve_autoscale_startup_timeout_s"),
    # -- llm
    Knob("RAY_TPU_PD_EXPORT_TTL_S", "float", 600.0,
         "Device-plane auto-release backstop for P/D prefill KV exports whose "
         "decode consumer crashed before acking.",
         "llm", attr="pd_export_ttl_s"),
    Knob("RAY_TPU_PD_EXPORT_MAX_LIVE", "int", 128,
         "Max un-acked P/D KV exports a prefill engine pins before LRU "
         "pruning (each pins device memory until the decode side pulls).",
         "llm", attr="pd_export_max_live"),
    Knob("RAY_TPU_PD_PAGED", "bool", True,
         "P/D KV handoff rides the paged streaming path: prefill publishes "
         "the KV region on the striped data plane and decode pulls it "
         "page-by-page over multiple streams, overlapped with decode bursts. "
         "Off = the original monolithic single-stream device-plane export.",
         "llm", attr="pd_paged"),
    Knob("RAY_TPU_PD_PAGE_BYTES", "int", 1 << 20,
         "Page size of the paged P/D KV handoff: the unit one puller stream "
         "fetches per ranged pull. Smaller pages spread better across "
         "streams; larger pages amortize per-pull framing.",
         "llm", attr="pd_page_bytes"),
    Knob("RAY_TPU_PD_PULL_STREAMS", "int", 4,
         "Concurrent puller streams a decode replica uses for one paged KV "
         "handoff (also the minimum stream count the prefill side's data "
         "server is provisioned for).",
         "llm", attr="pd_pull_streams"),
    Knob("RAY_TPU_PD_FETCH_TIMEOUT_S", "float", 60.0,
         "Overall deadline for one paged P/D KV fetch; past it the decode "
         "side fails the transfer with a typed DevicePlaneError and the "
         "router replays the request on the host path.",
         "llm", attr="pd_fetch_timeout_s"),
    Knob("RAY_TPU_PD_STAGING_BUFFERS", "int", 2,
         "Max recycled paged-handoff staging buffers a decode process pools. "
         "A fresh destination buffer costs a zero-fill page-fault pass per "
         "handoff; recycling skips it. Each pooled buffer holds one "
         "handoff's KV bytes of host memory; 0 disables pooling.",
         "llm", attr="pd_staging_buffers"),
    Knob("RAY_TPU_LLM_ENGINE_IDLE_WAIT_S", "float", 0.05,
         "Engine scheduler-loop sleep when no slot is active (admission "
         "latency floor for the first request of a burst).",
         "llm", attr="llm_engine_idle_wait_s"),
    Knob("RAY_TPU_LLM_MAX_NUM_SEQS", "int", 8,
         "Default decode-slot count for LLMConfig (continuous batching width).",
         "llm", attr="llm_max_num_seqs"),
    Knob("RAY_TPU_LLM_MAX_MODEL_LEN", "int", 1024,
         "Default per-slot KV capacity for LLMConfig.",
         "llm", attr="llm_max_model_len"),
    Knob("RAY_TPU_LLM_FUSED_STEPS", "int", 0,
         "Default fused decode burst width when LLMConfig.num_decode_steps is "
         "unset: the engine runs this many decode+sample steps on device per "
         "host sync. 0 = auto-tune from the measured host round trip vs the "
         "measured device step time.",
         "llm", attr="llm_fused_steps"),
    Knob("RAY_TPU_LLM_FUSED_STEPS_MAX", "int", 32,
         "Upper bound for the auto-tuned fused decode burst width (bounds "
         "both K-token streaming granularity and the log2(K) compiled decode "
         "program count).",
         "llm", attr="llm_fused_steps_max"),
    Knob("RAY_TPU_LLM_FUSED_SYNC_TARGET", "float", 0.15,
         "Auto-tune target for the host-sync share of a decode burst: K is "
         "raised until host_round_trip/(host_round_trip + K*device_step) "
         "drops to this fraction (subject to llm_fused_steps_max).",
         "llm", attr="llm_fused_sync_target"),
    Knob("RAY_TPU_LLM_PREFIX_MIN_HIT_TOKENS", "int", 0,
         "Prefix-cache pay-or-skip floor: a warm prefill only uses the cache "
         "when the cached-token count reaches this. 0 = auto — skip when the "
         "predicted compute saving (hit tokens x measured per-token prefill "
         "time) is below the measured dispatch round trip.",
         "llm", attr="llm_prefix_min_hit_tokens"),
    # -- train
    Knob("RAY_TPU_TRAIN_V2_ENABLED", "bool", False,
         "Route trainers through the v2 controller (FailurePolicy/"
         "ScalingPolicy; reference RAY_TRAIN_V2_ENABLED).",
         "train", attr="train_v2_enabled"),
    Knob("RAY_TPU_TRAIN_RESTART_BACKOFF_S", "float", 1.0,
         "Base of the bounded exponential backoff between Train worker-group "
         "restarts (failure N sleeps base*2^(N-1), capped). 0 disables.",
         "train", attr="train_restart_backoff_s"),
    Knob("RAY_TPU_TRAIN_RESTART_BACKOFF_MAX_S", "float", 30.0,
         "Cap on the Train restart backoff.",
         "train", attr="train_restart_backoff_max_s"),
    Knob("RAY_TPU_STORAGE_PATH", "str", None,
         "Default experiment storage path (default: ~/ray_tpu_results).",
         "train", attr="storage_path"),
    # -- ops
    Knob("RAY_TPU_MOE_GROUP_SIZE", "int", 4096,
         "Tokens per MoE dispatch group: dispatch/combine tensors are "
         "[group, experts, capacity], so memory is O(tokens x group).",
         "ops", attr="moe_group_size"),
    Knob("RAY_TPU_CHUNKED_ATTENTION_MIN_LOGITS", "int", 1 << 20,
         "Sq*Skv above which non-pallas attention switches to the chunked "
         "online-softmax path (bounds the logits buffer on long context).",
         "ops", attr="chunked_attention_min_logits"),
    # -- observability
    Knob("RAY_TPU_METRICS_REPORT_INTERVAL_S", "float", 2.0,
         "Worker metric-snapshot push period to the head "
         "(reference metrics_report_interval_ms).",
         "observability", attr="metrics_report_interval_s"),
    Knob("RAY_TPU_TQDM_RENDER_INTERVAL_S", "float", 0.1,
         "Min seconds between driver-side tqdm_ray re-renders.",
         "observability", attr="tqdm_render_interval_s"),
    Knob("RAY_TPU_TRACING", "bool", False,
         "Enable OpenTelemetry-style span recording AND the hot-path "
         "telemetry event recorder (util/telemetry.py) at init.",
         "observability", attr="tracing"),
    Knob("RAY_TPU_TELEMETRY_RING_SIZE", "int", 8192,
         "Per-process telemetry ring-buffer capacity (events). Overflow drops "
         "the oldest events and logs a throttled warning at flush.",
         "observability", attr="telemetry_ring_size"),
    Knob("RAY_TPU_METRICS_SCRAPE_INTERVAL_S", "float", 5.0,
         "Head-side metrics-history scrape period: the merged cross-worker "
         "snapshot is sampled into a timestamped frame ring this often, "
         "feeding windowed rates/quantiles and the SLO engine. 0 disables "
         "the scraper.",
         "observability", attr="metrics_scrape_interval_s"),
    Knob("RAY_TPU_METRICS_HISTORY_SIZE", "int", 360,
         "Frames retained in the metrics-history ring (at the default 5 s "
         "scrape interval, 360 frames = 30 min of windowed history).",
         "observability", attr="metrics_history_size"),
    Knob("RAY_TPU_USAGE_STATS", "bool", False,
         "Record a local-only feature-usage summary in the session dir "
         "(never leaves the machine).",
         "observability", attr="usage_stats"),
    Knob("RAY_TPU_LP_DEBUG", "bool", False,
         "Verbose serve long-poll client logging.",
         "observability", attr="lp_debug"),
    Knob("RAY_TPU_DASHBOARD_PORT", "int", 8265,
         "Dashboard HTTP port (JSON API, /metrics exposition, web UI).",
         "observability", attr="dashboard_port"),
    Knob("RAY_TPU_CONTROL_NODE_AGG", "bool", True,
         "Node-agent metrics/telemetry pre-aggregation: each agent merges "
         "its local workers' pushes and ships ONE per-node delta per flush "
         "tick, making head-side scrape cost O(nodes) instead of "
         "O(workers). Off = agents relay every worker frame verbatim "
         "(the pre-PR-17 behavior; also the head's fallback for "
         "un-upgraded agents).",
         "observability", attr="control_node_agg"),
    Knob("RAY_TPU_CONTROL_NODE_FLUSH_S", "float", 2.0,
         "Node-agent aggregated-delta ship period (matches the worker "
         "metric report interval so history freshness is unchanged). The "
         "head's backpressure signal can widen the EFFECTIVE interval up "
         "to RAY_TPU_CONTROL_BACKPRESSURE_MAX_S.",
         "observability", attr="control_node_flush_s"),
    Knob("RAY_TPU_CONTROL_MAX_SERIES", "int", 1024,
         "Bounded-cardinality guard: max distinct label sets per metric "
         "(per-process registries AND the head-side merge). New label sets "
         "past the cap are dropped and counted in "
         "metrics_dropped_series_total — head memory stays bounded even "
         "when a tag value explodes (e.g. a request id mistakenly used as "
         "a label).",
         "observability", attr="control_max_series"),
    Knob("RAY_TPU_CONTROL_INLET_BOUND", "int", 256,
         "Control-RPC inlet backpressure bound: when more metrics/"
         "telemetry frames than this arrive at the head between two scrape "
         "ticks, the head raises its backpressure level and tells agents "
         "to widen their flush interval; below half the bound it steps "
         "back down. 0 disables backpressure.",
         "observability", attr="control_inlet_bound"),
    Knob("RAY_TPU_CONTROL_BACKPRESSURE_MAX_S", "float", 30.0,
         "Widest flush interval the head's backpressure signal may impose "
         "on node agents (the signal doubles the interval per level; "
         "level 0 clears back to the agent's own cadence).",
         "observability", attr="control_backpressure_max_s"),
    Knob("RAY_TPU_CONTROL_HISTORY_JOURNAL_FRAMES", "int", 24,
         "Metrics-history frames journaled through the GCS KV path after "
         "each scrape so SLO burn windows and the router's windowed-TTFT "
         "inputs survive a head restart (needs "
         "RAY_TPU_GCS_PERSISTENCE_PATH to persist across processes). "
         "0 disables the journal.",
         "observability", attr="control_history_journal_frames"),
    Knob("RAY_TPU_CONTROL_HISTORY_MAX_POINTS", "int", 120,
         "Max points per series in state.history_series()/ /api/history: "
         "longer windows are downsampled (stride-wise, newest kept) and "
         "the payload marked truncated, so `ray-tpu status --watch` never "
         "ships megabytes per refresh.",
         "observability", attr="control_history_max_points"),
    Knob("RAY_TPU_CONTROL_HISTORY_MAX_SERIES", "int", 64,
         "Max series entries in state.history_series()/ /api/history "
         "payloads before the rest are dropped and the payload marked "
         "truncated.",
         "observability", attr="control_history_max_series"),
    # -- autoscaler
    Knob("RAY_TPU_PROVISION_MAX_ATTEMPTS", "int", 4,
         "Inline create_node attempts for rate-limit/transient cloud errors "
         "before the failure escalates to the autoscaler backoff (reference "
         "gcp node.py retry loops).",
         "autoscaler", attr="provision_max_attempts"),
    Knob("RAY_TPU_PROVISION_BACKOFF_S", "float", 2.0,
         "Base for the jittered exponential inline-retry backoff in "
         "create_node.",
         "autoscaler", attr="provision_backoff_s"),
    Knob("RAY_TPU_LAUNCH_BACKOFF_MAX_S", "float", 600.0,
         "Cap on the autoscaler's per-node-type launch backoff after "
         "quota/stockout/permanent provision failures.",
         "autoscaler", attr="launch_backoff_max_s"),
    # -- chaos
    Knob("RAY_TPU_FAULT_INJECTION", "str", None,
         "Arm util/fault_injection.py fail points from the environment: "
         "'site=mode[@p=0.5][@n=3][@delay=0.1][@seed=7][;site2=...]' with "
         "mode error|delay|kill. Deterministic chaos for tests/drills; "
         "unset = every fail point is a no-op.",
         "chaos", attr="fault_injection"),
    Knob("RAY_TPU_HEAD_PID", "int", None,
         "Default target for ChaosController.kill_head() when no pid/Popen "
         "is passed: the standalone head process to SIGKILL in head-death "
         "chaos runs. Unset = kill_head requires an explicit target.",
         "chaos"),

    # -- core (worker plumbing + native build)
    Knob("RAY_TPU_NODE_IP", "str", None,
         "Operator override for the IP this node advertises to peers "
         "(device plane + data plane listeners); default: outbound-interface "
         "autodetection.",
         "core"),
    Knob("RAY_TPU_SANITIZE", "str", None,
         "Rebuild the native shm-store library under a sanitizer: "
         "address|thread|undefined (dev/debug; see _native/build.py).",
         "core"),
    Knob("RAY_TPU_WORKER_AUTHKEY", "str", None,
         "Hex authkey a spawned/containerized worker uses to dial back to "
         "its node (set by the worker pool at spawn).",
         "core", internal=True),
    Knob("RAY_TPU_WORKER_LOG_DIR", "str", None,
         "Directory a worker tees its stdout/stderr capture into (set by "
         "the node agent at spawn).",
         "core", internal=True),
    Knob("RAY_TPU_ARENA", "str", None,
         "Shared-memory arena name a worker attaches to (set per node; "
         "never shared across hosts).",
         "object-store", internal=True),
    # -- runtime-env (continued)
    Knob("RAY_TPU_DEFAULT_RUNTIME_ENV", "str", None,
         "JSON job-level default runtime env the head propagates to node "
         "agents (set by ray_tpu.init(runtime_env=...)).",
         "runtime-env", internal=True),
    # -- train (grad-sync worker knobs: GradSyncConfig.from_env/to_env)
    Knob("RAY_TPU_TRAIN_GRAD_SYNC_MODE", "str", "gspmd",
         "Gradient sync mode in the worker train step: gspmd/monolithic "
         "(implicit sync) or bucketed (overlapped per-bucket allreduce).",
         "train"),
    Knob("RAY_TPU_TRAIN_BUCKET_BYTES", "int", 4 * 1024 * 1024,
         "Max payload per gradient allreduce bucket (bucketed mode).",
         "train"),
    Knob("RAY_TPU_TRAIN_GRAD_SYNC_AXIS", "str", "dp",
         "Mesh axis the bucketed sync reduces over manually.",
         "train"),
    Knob("RAY_TPU_TRAIN_GRAD_COMPRESSION", "str", None,
         "int8 = on-device block-quantized gradient reduction.",
         "train"),
    Knob("RAY_TPU_TRAIN_GRAD_STOCHASTIC_ROUNDING", "bool", False,
         "Unbiased stochastic rounding in the int8 gradient quantizer.",
         "train"),
    Knob("RAY_TPU_TRAIN_QUANT_BLOCK_ELEMS", "int", 1024,
         "Elements per int8 scale block in the quantized reduction.",
         "train"),
    Knob("RAY_TPU_TRAIN_MIN_QUANT_ELEMS", "int", 256,
         "Gradient leaves smaller than this stay f32 under int8 compression.",
         "train"),
    Knob("RAY_TPU_TRAIN_SHARDED_UPDATE", "bool", False,
         "Cross-replica sharded (ZeRO-style) optimizer update.",
         "train"),
    Knob("RAY_TPU_TRAIN_UPDATE_AXES", "str", "dp,fsdp",
         "Mesh axes the sharded optimizer update shards state over.",
         "train"),
    # -- MPMD pipeline parallelism (train/mpmd_pipeline.py)
    Knob("RAY_TPU_PIPELINE_MICROBATCHES", "int", 4,
         "Microbatches per optimizer step in the MPMD pipeline runner "
         "(power of two keeps the 1/M cotangent exact in f32).",
         "train", attr="pipeline_microbatches"),
    Knob("RAY_TPU_PIPELINE_SCHEDULE", "str", "1f1b",
         "MPMD pipeline schedule: 1f1b (warmup/steady/cooldown, overlapped) "
         "or gpipe (all-forwards-then-all-backwards baseline).",
         "train", attr="pipeline_schedule"),
    Knob("RAY_TPU_PIPELINE_PREFETCH", "int", 2,
         "Microbatch blocks each stage pulls ahead of its schedule cursor "
         "(0 = unoverlapped transfers).",
         "train", attr="pipeline_prefetch"),
    Knob("RAY_TPU_PIPELINE_STREAMS", "int", 1,
         "Concurrent stripes per inter-stage block pull (ranged pull_into "
         "fan-out; blocks under 64 KiB always ride one stream).",
         "train", attr="pipeline_streams"),
    Knob("RAY_TPU_PIPELINE_TRANSPORT", "str", "auto",
         "Inter-stage activation transport: auto (device plane when this "
         "process has it, else host), host, or device.",
         "train", attr="pipeline_transport"),
    Knob("RAY_TPU_TRAIN_GRAD_SYNC_TELEMETRY", "bool", False,
         "Two-stage train step with per-bucket wait spans "
         "(train.step_phase telemetry).",
         "train"),
    Knob("RAY_TPU_TRAIN_JAX_INIT_TIMEOUT_S", "int", 60,
         "jax.distributed.initialize() deadline on a Train worker.",
         "train"),
    Knob("RAY_TPU_TRAIN_RANK", "str", None,
         "This Train worker's rank (set by the backend at worker setup).",
         "train", internal=True),
    Knob("RAY_TPU_TRAIN_WORLD_SIZE", "str", None,
         "Train worker-group world size (set by the backend).",
         "train", internal=True),
    Knob("RAY_TPU_TRAIN_COLLECTIVE_GROUP", "str", None,
         "Collective group name a Train worker joins for host-plane sync "
         "(set by the backend).",
         "train", internal=True),
    # -- rl (decoupled rollout/learn plane: rllib/rollout_plane.py)
    Knob("RAY_TPU_RL_QUEUE_DEPTH", "int", 8,
         "Bounded trajectory-block queue depth; when full the OLDEST "
         "announced block is evicted (freshest-data-wins).",
         "rl"),
    Knob("RAY_TPU_RL_MAX_BLOCK_LAG", "int", 4,
         "Max policy-version lag a block may have at take time; staler "
         "blocks are dropped (counted `expired`) instead of trained on.",
         "rl"),
    Knob("RAY_TPU_RL_CORRECTION", "str", "is_clip",
         "Off-policy correction for stale blocks: 'is_clip' (PPO ratio "
         "clipping over behaviour-policy GAE) or 'vtrace' (IMPALA-style "
         "current-policy V-trace targets).",
         "rl"),
    Knob("RAY_TPU_RL_WEIGHT_SYNC_INTERVAL", "int", 1,
         "Learner updates between weight broadcasts back over the "
         "zero-copy plane (workers adopt at block boundaries).",
         "rl"),
    Knob("RAY_TPU_RL_BLOCKS_PER_UPDATE", "int", 1,
         "Trajectory blocks consumed per learner update (rounded up to a "
         "multiple of num_learners).",
         "rl"),
    Knob("RAY_TPU_RL_TAKE_TIMEOUT_S", "float", 30.0,
         "How long one training step polls the block queue before "
         "returning empty-handed (learner-paced; never blocks workers).",
         "rl"),
    Knob("RAY_TPU_RL_PRODUCER_SLACK", "int", 2,
         "Queue depth beyond which rollout workers pace themselves instead "
         "of sampling blocks destined for eviction (<= 0: free-run).",
         "rl"),
    Knob("RAY_TPU_RL_HOST_SLICING", "bool", False,
         "Force the legacy host-side minibatch slicing path in "
         "Learner.update (one H2D copy per minibatch) — bench/debug only; "
         "default is device-resident gather.",
         "rl"),
    # -- storage / test hooks
    Knob("RAY_TPU_MOCK_FS_ROOT", "str", None,
         "Backing directory for the mock:// checkpoint filesystem "
         "(storage tests; default: a tempdir).",
         "train"),
    # -- bench gates (read by core_bench.py, not the runtime)
    Knob("RAY_TPU_TELEMETRY_OVERHEAD_PCT", "float", 3.0,
         "core_bench --telemetry-overhead gate: max hot-path overhead "
         "percent with telemetry on.",
         "bench"),
    Knob("RAY_TPU_CONTROL_P99_MS", "float", 250.0,
         "core_bench --control-plane gate: max p99 scrape->SLO->autoscaler "
         "decision latency (ms) at 1024 synthetic replicas.",
         "bench"),
    Knob("RAY_TPU_CONTROL_AGG_SPEEDUP", "float", 4.0,
         "core_bench --control-plane gate: min head-side cost ratio "
         "(per-worker scrape / node-delta scrape) at 256 synthetic "
         "replicas — node aggregation must be at least this much cheaper.",
         "bench"),
    Knob("RAY_TPU_SCRAPE_OVERHEAD_PCT", "float", 1.0,
         "core_bench --scrape-overhead gate: max pull-path interference "
         "percent from the metrics-history scraper.",
         "bench"),
    Knob("RAY_TPU_TEST_POOL", "str", None,
         "Marker env var the worker-per-env pool tests key pools on "
         "(no runtime meaning).",
         "bench", internal=True),
]


REGISTRY: Dict[str, Knob] = {k.env: k for k in KNOBS}
assert len(REGISTRY) == len(KNOBS), "duplicate knob env names"

SUBSYSTEMS: List[str] = []
for _k in KNOBS:
    if _k.subsystem not in SUBSYSTEMS:
        SUBSYSTEMS.append(_k.subsystem)


def get(env: str) -> Optional[Knob]:
    return REGISTRY.get(env)


def by_subsystem(subsystem: str) -> List[Knob]:
    return [k for k in KNOBS if k.subsystem == subsystem]


def _default_repr(k: Knob) -> str:
    if k.default is None:
        return "unset"
    if k.type == "bool":
        return "on" if k.default else "off"
    return str(k.default)


def render_table(subsystem: str) -> str:
    """One markdown knob table for a subsystem (internal entries are listed
    last and tagged; they are protocol, not operator flags)."""
    rows = sorted(by_subsystem(subsystem), key=lambda k: (k.internal, k.env))
    lines = ["| knob | type | default | description |",
             "|---|---|---|---|"]
    for k in rows:
        doc = k.doc.replace("|", "\\|")
        if k.internal:
            doc = "*(internal: set by the runtime, not an operator flag)* " + doc
        lines.append(f"| `{k.env}` | {k.type} | `{_default_repr(k)}` | {doc} |")
    return "\n".join(lines)


# README generation: everything between a `<!-- knobs:<subsystem> -->` /
# `<!-- /knobs -->` marker pair is owned by this registry. `ray-tpu lint`
# fails on drift; `ray-tpu lint --write-docs` rewrites the blocks in place.
_BEGIN = "<!-- knobs:{sub} (generated from ray_tpu/knobs.py — do not edit) -->"
_END = "<!-- /knobs -->"


def render_block(subsystem: str) -> str:
    return "\n".join([_BEGIN.format(sub=subsystem), render_table(subsystem), _END])


def generate_readme(text: str) -> str:
    """Rewrite every marked knob block in `text` from the live registry."""
    import re

    def _sub(m: "re.Match[str]") -> str:
        return render_block(m.group(1))

    pat = re.compile(
        r"<!-- knobs:([a-z-]+) \(generated from ray_tpu/knobs\.py[^>]*-->"
        r".*?<!-- /knobs -->",
        re.S)
    return pat.sub(_sub, text)
