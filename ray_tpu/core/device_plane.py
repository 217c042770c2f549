"""Device-native tensor transfer between actor processes (the NCCL-channel analogue).

Capability parity: reference python/ray/experimental/gpu_object_manager/
gpu_object_manager.py:54 and python/ray/experimental/channel/
torch_tensor_nccl_channel.py — tensors stay resident on the accelerator and move
peer-to-peer on demand; only a small descriptor rides the control plane.

TPU shape of the idea: each process runs a PJRT *transfer server*
(`jax.experimental.transfer`, the DCN cross-slice transfer engine). A producer
`export()`s a pytree of jax.Arrays, getting a small picklable `DeviceHandle`; any
number of consumer processes `fetch()` it. Fetch arms a one-shot pull on the
producer via a per-process *arm server* (each consumer gets its own transfer uuid
— the PJRT protocol is strictly one pull per uuid), then pulls the buffers
device-to-device: on TPU pods the bytes ride DCN between hosts and never touch
Python, pickle, or the object store; the sandbox CPU backend uses the same socket
bulk-transport path.

Why an arm server instead of arming at export time: a pull consumes its uuid and
a stale uuid poisons the whole connection, so the number of consumers must not be
guessed up front. The arm round-trip is a ~1 KB control message; payload bytes
move exclusively through the transfer server.

Sharding contract: a NamedSharding is re-built on the consumer from (axis names,
mesh shape, partition spec) over `jax.devices()` in default order. When the
consumer cannot host the producer's mesh (fewer devices — e.g. a small decode
pool pulling from a big prefill pool), fetch falls back to a RESHARDING pull:
the producer arms its per-shard pieces, the consumer pulls each piece
device-to-device onto its own devices, and one compiled assemble program
scatters the pieces into an array sharded over a consumer-sized mesh (same axis
names, sizes shrunk to fit). Payload bytes still never touch host pickle.
"""
from __future__ import annotations

import functools
import secrets
import socket
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.config import CONFIG


class DevicePlaneError(RuntimeError):
    """Fetch could not complete device-natively; callers fall back to host bytes."""


class _NoClusterSession(RuntimeError):
    """The plane was asked for before any cluster session wrote its authkey: it stays off for now, not for the process."""


# ------------------------------------------------------------------ descriptors

@dataclass(frozen=True)
class ArraySpec:
    shape: Tuple[int, ...]
    dtype: str
    sharding: Tuple  # ("single",) | ("named", axis_names, mesh_shape, spec_entries)
    nbytes: int


@dataclass(frozen=True)
class DeviceHandle:
    """Small picklable descriptor of an exported device pytree."""

    arm_host: str
    arm_port: int
    key: bytes
    specs: Tuple[ArraySpec, ...]
    treedef_pickle: bytes  # jax treedefs pickle fine; kept opaque here
    nbytes: int


@dataclass(frozen=True)
class PagedKVHandle:
    """Descriptor of a block-addressable paged export (P/D KV handoff).

    Unlike DeviceHandle (one whole-buffer PJRT pull), the payload is published
    on the striped collective data plane as one segment per flat array, and
    consumers issue ranged multi-stream page pulls against (data_host,
    data_port). The arm channel is kept for control only: liveness probes
    ("stat") and release acks ride it, payload bytes never do."""

    arm_host: str
    arm_port: int
    data_host: str
    data_port: int
    key: bytes
    specs: Tuple[ArraySpec, ...]
    treedef_pickle: bytes
    nbytes: int
    page_bytes: int

    @property
    def n_pages(self) -> int:
        return max(1, -(-self.nbytes // self.page_bytes))

    def segments(self) -> Tuple[Tuple[str, int, int], ...]:
        """(store_key, global_offset, nbytes) per flat array, in spec order —
        the region's address map, derived so the handle stays small."""
        out, off = [], 0
        hexkey = self.key.hex()
        for i, s in enumerate(self.specs):
            out.append((f"pdkv:{hexkey}:{i}", off, s.nbytes))
            off += s.nbytes
        return tuple(out)


def _describe_sharding(arr) -> Tuple:
    sh = getattr(arr, "sharding", None)
    if sh is None:  # host numpy leaf (paged exports accept plain ndarrays)
        return ("single",)
    from jax.sharding import NamedSharding

    if isinstance(sh, NamedSharding) and len(sh.mesh.devices.flat) > 1:
        spec_entries = tuple(
            tuple(e) if isinstance(e, (tuple, list)) else e for e in tuple(sh.spec)
        )
        return ("named", tuple(sh.mesh.axis_names), tuple(sh.mesh.devices.shape),
                spec_entries)
    return ("single",)


def _rebuild_sharding(desc: Tuple):
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

    if desc[0] == "named":
        _, axis_names, mesh_shape, spec_entries = desc
        n = int(np.prod(mesh_shape))
        devs = jax.devices()
        if len(devs) < n:
            raise DevicePlaneError(
                f"consumer has {len(devs)} devices, producer mesh needs {n}")
        mesh = Mesh(np.asarray(devs[:n]).reshape(mesh_shape), axis_names)
        spec = PartitionSpec(*spec_entries)
        return NamedSharding(mesh, spec)
    return SingleDeviceSharding(_default_device())


def _fit_target_sharding(desc: Tuple, shape: Tuple[int, ...]):
    """A consumer-sized stand-in for a producer sharding the consumer can't
    host: same axis names and partition spec, mesh sizes shrunk (halving the
    largest axes) until the consumer's devices suffice. Spec axes that no
    longer divide the array dims drop to replicated."""
    import functools
    import operator

    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    _, axis_names, mesh_shape, spec_entries = desc
    n = len(jax.devices())
    sizes = list(mesh_shape)
    while functools.reduce(operator.mul, sizes, 1) > n:
        i = max(range(len(sizes)), key=lambda j: sizes[j])
        if sizes[i] <= 1:
            raise DevicePlaneError("cannot fit producer mesh on consumer")
        sizes[i] = sizes[i] // 2 if sizes[i] % 2 == 0 else 1
    total = functools.reduce(operator.mul, sizes, 1)
    mesh = Mesh(np.asarray(jax.devices()[:total]).reshape(sizes), axis_names)
    by_name = dict(zip(axis_names, sizes))

    def _entry_ok(entry, dim):
        names = entry if isinstance(entry, tuple) else (entry,)
        span = functools.reduce(operator.mul, (by_name.get(a, 1) for a in names), 1)
        return dim % span == 0

    entries = []
    for i, entry in enumerate(spec_entries):
        if entry is None or i >= len(shape):
            entries.append(None)
        else:
            entries.append(entry if _entry_ok(entry, shape[i]) else None)
    return NamedSharding(mesh, PartitionSpec(*entries))


@functools.lru_cache(maxsize=256)
def _assemble_program(starts_list: Tuple, block_shape: Tuple, dtype: str, dev):
    """Compiled single-device scatter-assemble: the pieces of ONE target shard
    (already pulled onto their owning device) -> that shard's block. Cached per
    (piece layout, shape, device) so steady-state fetches replay."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(dev)

    def build(pieces):
        out = jnp.zeros(block_shape, jnp.dtype(dtype))
        for p, st in zip(pieces, starts_list):
            out = jax.lax.dynamic_update_slice(out, p.astype(out.dtype), st)
        return out

    return jax.jit(build, in_shardings=([sh] * len(starts_list),),
                   out_shardings=sh)


class _ReshardPlan:
    """Where each producer piece lands and how target shards assemble."""

    def __init__(self, target, pieces, groups):
        self.target = target
        # meta-order: (piece_shape, global_starts, owning consumer device)
        self.pieces = pieces
        # one per DISTINCT target shard: ((start, stop) per dim, [devices
        # holding this shard], [piece indices covering it])
        self.groups = groups

    def assemble(self, pulled: List, spec: ArraySpec):
        import jax

        shape = tuple(spec.shape)
        blocks = []
        for key, devs, pidx in self.groups:
            local_shape = tuple(b - a for a, b in key)
            primary = devs[0]
            if len(pidx) == 1 and tuple(self.pieces[pidx[0]][0]) == local_shape:
                block = pulled[pidx[0]]
            else:
                starts_local = tuple(
                    tuple(s - a for s, (a, _b) in zip(self.pieces[i][1], key))
                    for i in pidx)
                prog = _assemble_program(starts_local, local_shape, spec.dtype,
                                         primary)
                block = prog([pulled[i] for i in pidx])
            blocks.append(block)
            for extra in devs[1:]:  # replicated target dims: device-to-device copy
                blocks.append(jax.device_put(block, extra))
        if len(blocks) == 1 and not isinstance(
                self.target, jax.sharding.NamedSharding):
            return blocks[0]
        return jax.make_array_from_single_device_arrays(
            shape, self.target, blocks)


def _reshard_plan(spec: ArraySpec, per_arr: List) -> _ReshardPlan:
    """Assign producer pieces to the consumer devices owning their slices of
    the shrunk-mesh target sharding; raises DevicePlaneError (-> host fallback)
    when the pieces don't nest exactly."""
    import math

    import jax
    from jax.sharding import SingleDeviceSharding

    shape = tuple(spec.shape)
    if spec.sharding[0] != "named":
        dev = jax.devices()[0]
        pieces = [(tuple(ps), tuple(st), dev) for ps, st in per_arr]
        key = tuple((0, d) for d in shape)
        return _ReshardPlan(SingleDeviceSharding(dev), pieces,
                            [(key, [dev], list(range(len(per_arr))))])
    target = _fit_target_sharding(spec.sharding, shape)
    groups: Dict[Tuple, List] = {}
    order: List[Tuple] = []
    for dev, idx in target.devices_indices_map(shape).items():
        key = tuple(
            (sl.start or 0, sl.stop if sl.stop is not None else dim)
            for sl, dim in zip(idx, shape))
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(dev)
    pieces, assign = [], {key: [] for key in order}
    for pi, (pshape, starts) in enumerate(per_arr):
        rng = tuple((s, s + d) for s, d in zip(starts, pshape))
        home = next(
            (key for key in order
             if all(a >= ka and b <= kb
                    for (a, b), (ka, kb) in zip(rng, key))), None)
        if home is None:
            raise DevicePlaneError(
                "producer shard does not nest inside the consumer sharding")
        assign[home].append(pi)
        pieces.append((tuple(pshape), tuple(starts), groups[home][0]))
    for key, pidx in assign.items():
        vol = sum(math.prod(per_arr[i][0]) for i in pidx)
        tvol = math.prod(b - a for a, b in key) if key else 1
        if vol != tvol:
            raise DevicePlaneError(
                "target shard not exactly covered by producer pieces")
    return _ReshardPlan(target, pieces,
                        [(key, groups[key], assign[key]) for key in order])


def _default_device():
    import jax

    return jax.devices()[0]


def _node_ip() -> str:
    import os

    ip = os.environ.get("RAY_TPU_NODE_IP")
    if ip:
        return ip
    try:
        # UDP connect trick: finds the outbound interface without sending.
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect(("8.8.8.8", 80))
            return s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        return "127.0.0.1"


# ------------------------------------------------------------------ the plane

class DevicePlane:
    """Per-process transfer endpoint: exports, arms, and pulls device pytrees."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._server = None  # PJRT TransferServer
        self._xfer_addr: Optional[str] = None
        self._arm_listener = None
        self._arm_addr: Optional[Tuple[str, int]] = None
        self._authkey: Optional[bytes] = None
        self._exports: Dict[bytes, List[Any]] = {}  # key -> flat arrays (pinned)
        # Opt-in TTL backstop (ADVICE r4): exports whose consumer might crash
        # without acking (P/D KV handoffs) pass export(ttl_s=...) and get swept
        # here if never released. Exports with a live OWNER that releases them
        # deterministically (device objects freed by the object store,
        # DeviceChannel values released on the next write) pass no TTL and stay
        # pinned until release() — a sweep there would DESTROY live data.
        self._export_deadlines: Dict[bytes, float] = {}
        # paged exports: key -> collective-plane store keys holding the host
        # copy of the KV region (one per flat array); released the same ways
        # _exports is (explicit, consumer ack, TTL sweep)
        self._paged_exports: Dict[bytes, List[str]] = {}
        # release subscribers (engine-level export bookkeeping): fired with the
        # key after ANY release, outside the plane lock
        self._release_listeners: List[Any] = []
        self._ttl_thread: Optional[threading.Thread] = None
        self._conns: Dict[str, Any] = {}  # xfer addr -> TransferConnection
        # arm addr -> pooled control conns (see _control: dial+challenge reuse)
        self._control_pool: Dict[Tuple[str, int], List[Any]] = {}
        self._uuid_counter = secrets.randbits(48) << 14  # process-unique uuid space
        self.counters: Dict[str, int] = {
            "exports": 0, "arms": 0, "pulls": 0, "bytes_pulled": 0, "fallbacks": 0,
        }
        self._disabled_reason: Optional[str] = None
        self._control_disabled_reason: Optional[str] = None

    # -- lifecycle ---------------------------------------------------------------

    def _ensure_started(self) -> None:
        if self._server is not None or self._disabled_reason:
            return
        with self._lock:
            if self._server is not None or self._disabled_reason:
                return
            try:
                self._start_locked()
            except _NoClusterSession:
                return  # (as `_ensure_control_started`: asked again once a session exists)
            except Exception as e:  # no transfer support on this backend/build
                self._disabled_reason = f"{type(e).__name__}: {e}"

    def _ensure_control_started(self) -> None:
        """Start just the arm/control channel (authkey + listener). The paged
        KV handoff moves payload over the striped socket data plane, so it
        stays available on backends whose jax build lacks PJRT transfer
        support — only whole-buffer device fetches need the transfer server."""
        if self._arm_listener is not None or self._control_disabled_reason:
            return
        with self._lock:
            if self._arm_listener is not None or self._control_disabled_reason:
                return
            try:
                self._start_control_locked()
            except _NoClusterSession:
                # not latched: a cluster that starts later in this process brings the key (a first touch before any
                # session, e.g. an engine's host-bytes handoff, must not disable paged handoff for the process)
                return
            except Exception as e:
                self._control_disabled_reason = f"{type(e).__name__}: {e}"

    def _start_control_locked(self) -> None:
        from ray_tpu.core.secure_transport import make_listener
        from ray_tpu.util.client.server import load_authkey

        authkey = load_authkey()
        if authkey is None:
            # Never MINT a key here: two peers racing generate_authkey() would
            # persist different session keys and every fetch would fail auth.
            # No cluster session -> no plane (callers fall back to host bytes).
            raise _NoClusterSession(
                "no cluster session authkey (set RAY_TPU_CLIENT_AUTHKEY or "
                "init a cluster first)")
        ip = _node_ip()
        listener = make_listener((ip, 0), backlog=64)
        self._authkey = authkey
        self._arm_listener = listener
        self._arm_addr = (ip, listener.address[1])
        threading.Thread(target=self._arm_loop, daemon=True,
                         name="rt-device-plane-arm").start()

    def _start_locked(self) -> None:
        import jax
        from jax.experimental import transfer

        if self._arm_listener is None:
            self._start_control_locked()
        ip = self._arm_addr[0]
        client = jax.devices()[0].client
        # Explicit socket transport addresses: the default same-host "local" bulk
        # transport is not implemented for all backends (CHECK-fails on CPU), and
        # cross-host always needs routable sockets anyway.
        server = transfer.start_transfer_server(
            client, f"{ip}:0", [f"{ip}:0"])
        self._server = server
        self._xfer_addr = server.address()

    @property
    def available(self) -> bool:
        if not CONFIG.device_plane:
            return False
        self._ensure_started()
        return self._server is not None

    @property
    def paged_available(self) -> bool:
        """Can this process produce/consume paged exports? Needs only the
        control channel + striped data plane, not PJRT transfer support."""
        if not CONFIG.device_plane:
            return False
        self._ensure_control_started()
        return self._arm_listener is not None

    @property
    def disabled_reason(self) -> Optional[str]:
        return self._disabled_reason

    # -- producer side -----------------------------------------------------------

    def export(self, tree: Any, ttl_s: Optional[float] = None) -> DeviceHandle:
        """Register a pytree of jax.Arrays for device-native fetch by peers.

        The plane holds strong references until `release(handle.key)` — exports
        pin device memory, so producers release as soon as consumers are done
        (P/D: when the decode side acks; channels: on next write). ttl_s, when
        given, additionally auto-releases the export after that long — the
        crashed-consumer backstop for fire-and-forget handoffs; leave it None
        for exports an owner releases deterministically.
        """
        if not self.available:
            raise DevicePlaneError(self._disabled_reason or "device plane disabled")
        import jax
        import pickle

        flat, treedef = jax.tree.flatten(tree)
        if not flat:
            raise DevicePlaneError("empty pytree")
        # the transfer server arms jax Arrays only (jax 0.9 rejects a host
        # ndarray in the list); a caller's numpy block goes onto the device
        flat = [x if isinstance(x, jax.Array) else jax.numpy.asarray(x) for x in flat]
        specs = tuple(
            ArraySpec(tuple(x.shape), str(x.dtype), _describe_sharding(x), x.nbytes)
            for x in flat
        )
        key = secrets.token_bytes(16)
        with self._lock:
            self._exports[key] = flat
            self.counters["exports"] += 1
            if ttl_s is not None:
                self._export_deadlines[key] = time.monotonic() + ttl_s
                if self._ttl_thread is None:
                    self._ttl_thread = threading.Thread(
                        target=self._ttl_loop, daemon=True,
                        name="rt-device-plane-ttl")
                    self._ttl_thread.start()
        host, port = self._arm_addr
        return DeviceHandle(
            arm_host=host, arm_port=port, key=key, specs=specs,
            treedef_pickle=pickle.dumps(treedef),
            nbytes=sum(s.nbytes for s in specs))

    def export_paged(self, tree: Any, ttl_s: Optional[float] = None,
                     page_bytes: Optional[int] = None) -> PagedKVHandle:
        """Register a pytree as a block-addressable region for ranged,
        multi-stream page pulls (the P/D KV handoff fast path).

        PJRT transfer pulls are whole-buffer only, so the region is gathered
        to host once here and published segment-per-array on the striped
        collective data plane; consumers pull pages concurrently over
        CONFIG.pd_pull_streams sockets, overlapped with their own decode
        bursts. Same lifetime contract as export(): pinned (host-side) until
        release()/consumer ack, with ttl_s as the crashed-consumer backstop.
        """
        if not self.paged_available:
            raise DevicePlaneError(
                self._control_disabled_reason or "device plane disabled")
        import pickle

        import jax
        import numpy as np

        from ray_tpu.util.collective import ring

        flat, treedef = jax.tree.flatten(tree)
        if not flat:
            raise DevicePlaneError("empty pytree")
        specs = tuple(
            ArraySpec(tuple(x.shape), str(x.dtype), _describe_sharding(x), x.nbytes)
            for x in flat
        )
        key = secrets.token_bytes(16)
        page = int(page_bytes or CONFIG.pd_page_bytes)
        # the producer's data server must carry at least one consumer's worth
        # of concurrent page streams without starving collective traffic
        cplane = ring.get_plane(self._authkey,
                                min_streams=max(1, CONFIG.pd_pull_streams))
        seg_keys: List[str] = []
        hexkey = key.hex()
        for i, x in enumerate(flat):
            host_arr = np.ascontiguousarray(np.asarray(x))
            skey = f"pdkv:{hexkey}:{i}"
            # exp=0: the consumer may re-probe ranges; lifetime is ours —
            # retracted on release(), TTL sweep is only the backstop
            cplane.publish(skey, memoryview(host_arr).cast("B"), 0)
            seg_keys.append(skey)
        with self._lock:
            self._paged_exports[key] = seg_keys
            self.counters["exports"] += 1
            self.counters["paged_exports"] = (
                self.counters.get("paged_exports", 0) + 1)
            if ttl_s is not None:
                self._export_deadlines[key] = time.monotonic() + ttl_s
                if self._ttl_thread is None:
                    self._ttl_thread = threading.Thread(
                        target=self._ttl_loop, daemon=True,
                        name="rt-device-plane-ttl")
                    self._ttl_thread.start()
        host, port = self._arm_addr
        return PagedKVHandle(
            arm_host=host, arm_port=port,
            data_host=cplane.addr[0], data_port=cplane.addr[1],
            key=key, specs=specs, treedef_pickle=pickle.dumps(treedef),
            nbytes=sum(s.nbytes for s in specs), page_bytes=page)

    def add_release_listener(self, cb) -> None:
        """Subscribe cb(key: bytes) to export releases (explicit, consumer
        ack over the arm channel, or TTL sweep). Fired outside the plane lock;
        engine-level export bookkeeping syncs on this instead of polling."""
        with self._lock:
            self._release_listeners.append(cb)

    def release(self, key: bytes) -> None:
        with self._lock:
            found = (self._exports.pop(key, None) is not None)
            seg_keys = self._paged_exports.pop(key, None)
            found = found or seg_keys is not None
            self._export_deadlines.pop(key, None)
            listeners = list(self._release_listeners) if found else []
        if seg_keys:
            try:
                from ray_tpu.util.collective import ring

                cplane = ring.get_plane(self._authkey)
                for skey in seg_keys:
                    cplane.retract(skey)
            # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
            except Exception:
                pass
        for cb in listeners:
            try:
                cb(key)
            # graftlint: allow[swallowed-exception] callback isolation: a throwing subscriber must not break the caller
            except Exception:
                pass

    def _ttl_loop(self, interval_s: float = 30.0) -> None:
        while True:
            time.sleep(interval_s)
            now = time.monotonic()
            with self._lock:
                stale = [k for k, d in self._export_deadlines.items()
                         if now > d]
            for k in stale:
                # through release(): paged store keys retract and release
                # listeners fire for TTL sweeps too
                self.release(k)

    def _arm_loop(self) -> None:
        while True:
            try:
                conn = self._arm_listener.accept()
            except EOFError:
                continue  # one bad/failed dial (TLS probe) must not stop serving
            except OSError:
                return  # listener closed
            threading.Thread(target=self._serve_arm, args=(conn,), daemon=True,
                             name="rt-device-plane-serve").start()

    def _serve_arm(self, conn) -> None:
        from multiprocessing.connection import deliver_challenge, answer_challenge
        import pickle

        from ray_tpu.core.secure_transport import set_nodelay

        try:
            # control ops are tiny request/response pairs; without NODELAY each
            # one eats a Nagle + delayed-ACK stall (~40 ms on loopback)
            set_nodelay(conn.fileno())
            deliver_challenge(conn, self._authkey)
            answer_challenge(conn, self._authkey)
            while True:
                op, key = pickle.loads(conn.recv_bytes())
                if op == "release":
                    self.release(key)
                    conn.send_bytes(pickle.dumps(("ok",)))
                    continue
                if op == "stat":
                    # liveness probe for paged fetches: lets the consumer fail
                    # a dead/released export eagerly instead of blocking a
                    # ranged pull on a range that will never publish
                    with self._lock:
                        live = key in self._exports or key in self._paged_exports
                    conn.send_bytes(pickle.dumps(("ok",) if live else ("gone",)))
                    continue
                if op not in ("arm", "arm_shards"):
                    conn.send_bytes(pickle.dumps(("err", f"bad op {op!r}")))
                    continue
                if self._server is None:
                    # control-only start (paged handoff on a backend without
                    # PJRT transfer support): whole-buffer pulls can't arm
                    conn.send_bytes(pickle.dumps(
                        ("err", "no PJRT transfer server")))
                    continue
                with self._lock:
                    flat = self._exports.get(key)
                    if flat is None:
                        conn.send_bytes(pickle.dumps(("gone",)))
                        continue
                    self._uuid_counter += 1
                    uuid = self._uuid_counter
                    self.counters["arms"] += 1
                if op == "arm_shards":
                    # resharding pull: arm the per-shard PIECES so a consumer
                    # with a different device topology can pull them one by
                    # one and reassemble under its own mesh
                    pieces, meta = [], []
                    for arr in flat:
                        per_arr, seen = [], set()
                        for sh in arr.addressable_shards:
                            starts = tuple(int(sl.start or 0) for sl in sh.index)
                            if starts in seen:  # replicated copy of a piece
                                continue
                            seen.add(starts)
                            pieces.append(sh.data)
                            per_arr.append((tuple(sh.data.shape), starts))
                        meta.append(per_arr)
                    self._server.await_pull(uuid, pieces)
                    conn.send_bytes(pickle.dumps(
                        ("ok", self._xfer_addr, uuid, meta)))
                    continue
                # await_pull holds buffer refs in the server until pulled.
                self._server.await_pull(uuid, flat)
                conn.send_bytes(pickle.dumps(("ok", self._xfer_addr, uuid)))
        except (EOFError, OSError, pickle.UnpicklingError):
            pass
        finally:
            try:
                conn.close()
            # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
            except Exception:
                pass

    # -- consumer side -----------------------------------------------------------

    def fetch(self, handle: DeviceHandle, release: bool = False) -> Any:
        """Pull an exported pytree device-to-device. Raises DevicePlaneError on any
        failure (producer gone, topology mismatch) — callers fall back to host.

        release=True acks the producer after a successful pull so it drops its
        pinned export immediately (single-consumer handoffs like P/D KV)."""
        if not self.available:
            with self._lock:
                self.counters["fallbacks"] += 1
            raise DevicePlaneError(self._disabled_reason or "device plane disabled")
        import jax
        import pickle

        try:
            try:
                shardings = [_rebuild_sharding(s.sharding) for s in handle.specs]
            except DevicePlaneError:
                # consumer can't host the producer's mesh (e.g. a 2-chip decode
                # pool pulling from a 4-chip prefill pool): per-shard pull +
                # compiled reassembly under a consumer-sized mesh
                return self._fetch_reshard(handle, release)
            xfer_addr, uuid = self._arm(handle)
            avals = [
                jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
                for s, sh in zip(handle.specs, shardings)
            ]
            conn = self._connection(xfer_addr)
            try:
                flat = conn.pull(uuid, avals)
            except Exception:
                # A failed pull poisons the PJRT connection: drop it so the next
                # fetch redials instead of inheriting a dead socket.
                with self._lock:
                    self._conns.pop(xfer_addr, None)
                raise
            with self._lock:
                self.counters["pulls"] += 1
                self.counters["bytes_pulled"] += handle.nbytes
            if release:
                try:
                    self._control(handle, ("release", handle.key))
                # graftlint: allow[swallowed-exception] callback isolation: a throwing subscriber must not break the caller
                except Exception:
                    pass  # producer TTL-prunes as backstop
            treedef = pickle.loads(handle.treedef_pickle)
            return jax.tree.unflatten(treedef, flat)
        except DevicePlaneError:
            with self._lock:
                self.counters["fallbacks"] += 1
            raise
        except Exception as e:
            with self._lock:
                self.counters["fallbacks"] += 1
            raise DevicePlaneError(f"device fetch failed: {type(e).__name__}: {e}") from e

    def _fetch_reshard(self, handle: DeviceHandle, release: bool) -> Any:
        """Pull a producer's per-shard pieces onto this process's devices and
        assemble them under a consumer-sized sharding — the unequal-topology
        half of the fetch contract (reference analogue: NCCL channels reshard
        between different-size P/D pools,
        experimental/channel/torch_tensor_nccl_channel.py).

        Each piece is pulled STRAIGHT to the consumer device that owns its
        slice of the target sharding (the shrunk-mesh producer spec always
        refines it along the same axes), then assembled per-device — payload
        bytes go producer-device -> owning consumer-device exactly once."""
        import pickle

        import jax
        from jax.sharding import SingleDeviceSharding

        resp = self._control(handle, ("arm_shards", handle.key))
        if resp[0] == "gone":
            raise DevicePlaneError("export released by producer")
        if resp[0] != "ok":
            raise DevicePlaneError(f"arm_shards failed: {resp!r}")
        _, xfer_addr, uuid, meta = resp
        plans = [
            _reshard_plan(spec, per_arr)
            for spec, per_arr in zip(handle.specs, meta)
        ]
        avals = [
            jax.ShapeDtypeStruct(shape, spec.dtype,
                                 sharding=SingleDeviceSharding(dev))
            for spec, plan in zip(handle.specs, plans)
            for shape, _starts, dev in plan.pieces
        ]
        conn = self._connection(xfer_addr)
        try:
            flat_pieces = conn.pull(uuid, avals)
        except Exception:
            with self._lock:
                self._conns.pop(xfer_addr, None)
            raise
        with self._lock:
            self.counters["pulls"] += 1
            self.counters["reshard_pulls"] = self.counters.get("reshard_pulls", 0) + 1
            self.counters["bytes_pulled"] += handle.nbytes
        if release:
            try:
                self._control(handle, ("release", handle.key))
            # graftlint: allow[swallowed-exception] callback isolation: a throwing subscriber must not break the caller
            except Exception:
                pass  # plane TTL-prunes as backstop
        arrays, pos = [], 0
        for spec, plan in zip(handle.specs, plans):
            pieces = flat_pieces[pos:pos + len(plan.pieces)]
            pos += len(plan.pieces)
            arrays.append(plan.assemble(pieces, spec))
        treedef = pickle.loads(handle.treedef_pickle)
        return jax.tree.unflatten(treedef, arrays)

    def fetch_paged(self, handle: PagedKVHandle, release: bool = False,
                    on_done=None) -> "PagedKVFetch":
        """Begin a multi-stream paged pull of an export_paged() region and
        return immediately with the in-flight PagedKVFetch — the caller
        overlaps its own work (decode bursts) with the transfer and collects
        the arrays via result() when it actually needs them.

        Fails EAGERLY (DevicePlaneError raised here) when the export is
        already gone — a liveness probe on the arm channel — so callers can
        fall back to the host path before anything streamed. Mid-transfer
        failures (producer SIGKILL, retraction, deadline) surface as
        DevicePlaneError from wait()/result() within the bounded-probe stall
        window, never as an indefinite hang.

        release=True acks the producer over the arm channel once the last
        page lands (single-consumer handoffs)."""
        if not self.paged_available:
            with self._lock:
                self.counters["fallbacks"] += 1
            raise DevicePlaneError(
                self._control_disabled_reason or "device plane disabled")
        try:
            resp = self._control(handle, ("stat", handle.key))
        except DevicePlaneError:
            with self._lock:
                self.counters["fallbacks"] += 1
            raise
        if resp[0] == "gone":
            with self._lock:
                self.counters["fallbacks"] += 1
            raise DevicePlaneError("export was released by the producer")
        if resp[0] != "ok":
            raise DevicePlaneError(f"stat failed: {resp!r}")
        return PagedKVFetch(self, handle, release=release, on_done=on_done)

    _CONTROL_POOL_MAX = 4  # pooled arm-channel conns kept per producer

    def _dial_control(self, addr: Tuple[str, int]):
        from ray_tpu.core.secure_transport import dial
        from ray_tpu.util.client.server import load_authkey

        authkey = self._authkey or load_authkey()
        if authkey is None:
            raise DevicePlaneError("no cluster session authkey")
        try:
            return dial(addr, authkey=authkey)
        except Exception as e:
            raise DevicePlaneError(f"producer unreachable: {e}") from e

    def _control(self, handle: DeviceHandle, msg: Tuple) -> Tuple:
        """One control round trip (arm/stat/release) on the producer's arm
        channel. Connections are pooled per producer: every dial pays a TCP
        connect + 2-round-trip authkey challenge, and the paged handoff path
        issues two control ops per request (liveness stat + release ack) — at
        serving rates the handshakes would dominate the ops themselves. A
        stale pooled connection (producer restarted, idle conn reaped) gets
        one retry on a fresh dial; the server arm loop serves any number of
        sequential ops per connection."""
        import pickle

        addr = (handle.arm_host, handle.arm_port)
        payload = pickle.dumps(msg)
        for attempt in (0, 1):
            conn = None
            if attempt == 0:  # the retry always dials fresh
                with self._lock:
                    free = self._control_pool.get(addr)
                    conn = free.pop() if free else None
            from_pool = conn is not None
            if conn is None:
                conn = self._dial_control(addr)
            try:
                conn.send_bytes(payload)
                resp = pickle.loads(conn.recv_bytes())
            except Exception as e:
                try:
                    conn.close()
                # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
                except Exception:
                    pass
                if from_pool and attempt == 0:
                    continue  # stale pooled conn: retry once on a fresh dial
                raise DevicePlaneError(f"producer unreachable: {e}") from e
            with self._lock:
                pool = self._control_pool.setdefault(addr, [])
                if len(pool) < self._CONTROL_POOL_MAX:
                    pool.append(conn)
                    conn = None
            if conn is not None:
                try:
                    conn.close()
                # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
                except Exception:
                    pass
            return resp

    def _arm(self, handle: DeviceHandle) -> Tuple[str, int]:
        resp = self._control(handle, ("arm", handle.key))
        if resp[0] == "gone":
            raise DevicePlaneError("export was released by the producer")
        if resp[0] != "ok":
            raise DevicePlaneError(f"arm failed: {resp!r}")
        return resp[1], resp[2]

    def _connection(self, xfer_addr: str):
        with self._lock:
            conn = self._conns.get(xfer_addr)
        if conn is not None:
            return conn
        conn = self._server.connect(xfer_addr)
        with self._lock:
            self._conns[xfer_addr] = conn
        return conn

    def stats(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self.counters)
        out["exports_live"] = len(self._exports) + len(self._paged_exports)
        return out


_staging_lock = threading.Lock()
_staging_bufs: List[Any] = []


def _staging_checkout(nbytes: int):
    """A staging buffer of at least `nbytes`: the smallest pooled buffer that
    fits, else a fresh uninitialized allocation. Pooled buffers matter on the
    ingest path — a decode replica fetches prefill KV continuously, and a
    fresh 256 MB destination costs a full zero-fill page-fault pass (~halves
    loopback throughput) that a recycled, already-faulted buffer skips."""
    with _staging_lock:
        best = None
        for i, b in enumerate(_staging_bufs):
            if b.nbytes >= nbytes and (
                    best is None or b.nbytes < _staging_bufs[best].nbytes):
                best = i
        if best is not None:
            return _staging_bufs.pop(best)
    import numpy as np

    # np.empty, not bytearray: bytearray(n) memsets the whole region up front
    # before a single page arrives; an uninitialized buffer lets the kernel
    # zero-fault pages under the readv()s instead, overlapped with the
    # network wait
    return np.empty(max(nbytes, 1), dtype=np.uint8)


def _staging_recycle(buf) -> None:
    with _staging_lock:
        if len(_staging_bufs) < max(0, int(CONFIG.pd_staging_buffers)):
            _staging_bufs.append(buf)


class PagedKVFetch:
    """One in-flight paged KV pull: up to CONFIG.pd_pull_streams puller
    threads (clamped to the page count and the host's CPU count — extra
    streams on a small host only add GIL/context-switch churn) stream the
    region's pages into a single host buffer while the consumer keeps
    decoding its active batch. Pages are claimed near-in-order off a shared
    counter, so the streams naturally load-balance across page-size variance
    and socket jitter.

    The destination is checked out of a process-level staging pool; call
    recycle() once the result() arrays have been copied out (device_put /
    jnp.asarray) so the next handoff reuses the already-faulted pages.

    Failure contract: any puller error (producer SIGKILL -> connection reset,
    export retracted mid-transfer -> bounded probe + stat says gone, overall
    CONFIG.pd_fetch_timeout_s deadline) resolves the fetch with a
    DevicePlaneError raised from wait()/result(); pullers use ~1 s bounded
    probes rather than full-op-timeout blocking reads, so the stall is
    detection-bounded, not timeout-bounded."""

    _PROBE_S = 1.0

    def __init__(self, dplane: "DevicePlane", handle: PagedKVHandle,
                 release: bool = False, on_done=None) -> None:
        import os

        from ray_tpu.util.collective import ring

        self._plane = dplane
        self.handle = handle
        self._release = release
        self._on_done = on_done
        self.nbytes = handle.nbytes
        self.page_bytes = handle.page_bytes
        self.n_pages = handle.n_pages
        self._segs = handle.segments()
        self._buf = _staging_checkout(handle.nbytes)
        self._mv = memoryview(self._buf)[:handle.nbytes]
        self._cv = threading.Condition()
        self._next_page = 0
        self._pages_done = 0
        self._error: Optional[DevicePlaneError] = None
        self._cancelled = False
        self._finished = False
        self.t0_wall_ns = time.time_ns()
        self._t0 = time.perf_counter()
        self.dur_s: Optional[float] = None
        self.streams = max(1, min(int(CONFIG.pd_pull_streams), self.n_pages,
                                  max(2, os.cpu_count() or 1)))
        self._cplane = ring.get_plane(dplane._authkey, min_streams=self.streams)
        for i in range(self.streams):
            threading.Thread(target=self._pull_loop, daemon=True,
                             name=f"rt-pd-pull-{i}").start()

    # -- puller side -------------------------------------------------------------

    def _pull_loop(self) -> None:
        from ray_tpu.util.fault_injection import fail_point

        addr = (self.handle.data_host, self.handle.data_port)
        deadline = self._t0 + float(CONFIG.pd_fetch_timeout_s)
        # claim contiguous RUNS of pages, not single pages: a failure kills the
        # whole fetch (there is no per-page retry), so page granularity buys
        # nothing per-claim — but every ranged pull costs a request/ok/go
        # handshake, and coalescing a stream's adjacent pages into one pull
        # amortizes it. ~4 claims per stream keeps the tail load-balanced.
        run_pages = max(1, -(-self.n_pages // (self.streams * 4)))
        while True:
            with self._cv:
                if (self._error is not None or self._cancelled
                        or self._next_page >= self.n_pages):
                    return
                page = self._next_page
                run = min(run_pages, self.n_pages - page)
                self._next_page += run
            try:
                # chaos site: armed with mode=delay this stretches the handoff
                # window (SIGKILL-the-producer tests), mode=error simulates a
                # torn pull
                fail_point("llm.pd.handoff", page=page,
                           key=self.handle.key.hex())
                self._pull_range(addr, page, run, deadline)
            except BaseException as e:
                err = e if isinstance(e, DevicePlaneError) else DevicePlaneError(
                    f"paged KV pull failed: {type(e).__name__}: {e}")
                if err is not e:
                    err.__cause__ = e
                first = False
                with self._cv:
                    if self._error is None and not self._finished:
                        self._error = err
                        first = True
                    self._cv.notify_all()
                if first:
                    self._resolve(ok=False)
                return
            done = False
            with self._cv:
                self._pages_done += run
                if self._pages_done >= self.n_pages and not self._finished:
                    self.dur_s = time.perf_counter() - self._t0
                    done = True
                self._cv.notify_all()
            if done:
                self._resolve(ok=True)
                return

    def _pull_range(self, addr, page: int, n_run: int, deadline: float) -> None:
        start = page * self.page_bytes
        end = min(start + n_run * self.page_bytes, self.nbytes)
        for skey, seg_off, seg_len in self._segs:
            lo, hi = max(start, seg_off), min(end, seg_off + seg_len)
            if lo >= hi:
                continue
            while True:
                with self._cv:
                    if self._error is not None or self._cancelled:
                        return
                n = self._cplane.pull_into(addr, skey, lo - seg_off, hi - lo,
                                           self._mv[lo:hi],
                                           timeout=self._PROBE_S)
                if n is not None:
                    break
                # bounded-probe miss: the range is published up front, so a
                # miss means the export was retracted (or the producer is
                # wedged) — probe liveness instead of pinning an op timeout
                resp = self._plane._control(self.handle,
                                            ("stat", self.handle.key))
                if resp[0] != "ok":
                    raise DevicePlaneError(
                        "export released by producer mid-transfer")
                if time.perf_counter() > deadline:
                    raise DevicePlaneError(
                        f"paged KV fetch exceeded "
                        f"{CONFIG.pd_fetch_timeout_s}s deadline")

    def _resolve(self, ok: bool) -> None:
        with self._cv:
            if self._finished:
                return
            self._finished = True
        with self._plane._lock:
            if ok:
                self._plane.counters["pulls"] += 1
                self._plane.counters["bytes_pulled"] += self.nbytes
                self._plane.counters["paged_pulls"] = (
                    self._plane.counters.get("paged_pulls", 0) + 1)
            else:
                self._plane.counters["fallbacks"] += 1
        if ok and self._release:
            self._ack_release()
        cb = self._on_done
        if cb is not None:
            try:
                cb()
            # graftlint: allow[swallowed-exception] callback isolation: a throwing subscriber must not break the caller
            except Exception:
                pass

    def _ack_release(self) -> None:
        try:
            self._plane._control(self.handle, ("release", self.handle.key))
        # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
        except Exception:
            pass  # producer TTL-prunes as backstop

    # -- consumer side -----------------------------------------------------------

    def failed(self) -> Optional[DevicePlaneError]:
        with self._cv:
            return self._error

    def ready(self) -> bool:
        """All pages landed (does not raise; pair with failed())."""
        with self._cv:
            return self._error is None and self._pages_done >= self.n_pages

    def pages_done(self) -> int:
        with self._cv:
            return self._pages_done

    def wait(self, timeout: Optional[float] = None) -> None:
        """Block until every page landed; raises DevicePlaneError on transfer
        failure or timeout."""
        deadline = time.monotonic() + (
            float(CONFIG.pd_fetch_timeout_s) if timeout is None else timeout)
        with self._cv:
            while True:
                if self._error is not None:
                    raise self._error
                if self._pages_done >= self.n_pages:
                    return
                left = deadline - time.monotonic()
                if left <= 0:
                    raise DevicePlaneError(
                        "timed out waiting for paged KV fetch")
                self._cv.wait(min(left, 1.0))

    def result(self, timeout: Optional[float] = None) -> Any:
        """The fetched pytree as zero-copy numpy views over the landed buffer
        (consumers device_put / jnp.asarray what they install)."""
        import pickle

        import jax
        import numpy as np

        self.wait(timeout)
        arrays = []
        for (skey, off, ln), spec in zip(self._segs, self.handle.specs):
            dt = np.dtype(spec.dtype)
            arrays.append(
                np.frombuffer(self._buf, dtype=dt, count=ln // dt.itemsize,
                              offset=off).reshape(spec.shape))
        treedef = pickle.loads(self.handle.treedef_pickle)
        return jax.tree.unflatten(treedef, arrays)

    def cancel(self, release: bool = True) -> None:
        """Abandon the transfer (consumer aborted the request): pullers stop
        at the next page/probe boundary; release=True still acks the producer
        so the export unpins without waiting for the TTL backstop."""
        with self._cv:
            if self._finished:
                return
            self._cancelled = True
            self._finished = True
            self._cv.notify_all()
        if release:
            self._ack_release()

    def recycle(self) -> None:
        """Return the staging buffer to the process pool. Call ONLY after the
        result() views have been copied out — they alias the buffer and the
        next fetch will overwrite it. No-op for a cancelled or failed fetch
        (a straggler puller may still be landing bytes into the buffer) and
        on double-recycle."""
        with self._cv:
            if (not self._finished or self._cancelled
                    or self._error is not None or self._buf is None):
                return
            buf, self._buf, self._mv = self._buf, None, None
        _staging_recycle(buf)


def release_remote(handle) -> None:
    """Release an export by dialing the exporting process's arm channel
    directly — pool-safe: a pool routes method calls p2c across replicas, so
    'release via the handle that prefilled' cannot be expressed as a
    deployment call, but the arm address on the handle pins the right
    process. Best-effort; raises DevicePlaneError only when no authkey/dial.
    """
    plane()._control(handle, ("release", handle.key))


_plane: Optional[DevicePlane] = None
_plane_lock = threading.Lock()


def plane() -> DevicePlane:
    global _plane
    if _plane is None:
        with _plane_lock:
            if _plane is None:
                _plane = DevicePlane()
    return _plane
