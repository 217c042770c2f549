"""Three-tier object store: inline bytes, C++ shared-memory arena, per-object segments.

Capability parity: reference plasma store (src/ray/object_manager/plasma/store.h:55) +
CoreWorker memory store (src/ray/core_worker/store_provider/). Differences by design:
- Large objects live in one node-wide C++ arena (_native/shm_store.cc): create/seal are
  library calls into shared memory, not a socket round-trip to a plasma daemon; the
  allocator is a boundary-tag heap (plasma uses dlmalloc behind a store process).
- When the arena is full or absent, producers fall back to creating a per-object POSIX
  shm segment themselves (this doubles as "spilling" pressure relief).
- Readers map zero-copy; numpy arrays deserialized from the arena or a segment are views
  over the mapping (pickle5 out-of-band buffers, see serialization.py).
"""
from __future__ import annotations

import os
import threading
from multiprocessing import shared_memory
from typing import Any, Dict, List, Optional, Tuple

from . import serialization
from .ids import ObjectID

from ray_tpu.config import memoized_flag

# Objects below this many serialized bytes travel inline through control
# pipes. Per-put fast path (~80k+ puts/s): memoized against the raw env string.
_inline_threshold = memoized_flag("inline_threshold_bytes")

# Location tuples:
#   ("inline", frame_bytes, is_error)
#   ("arena", arena_name, oid_bytes, nbytes, is_error)
#   ("shm", name, nbytes, is_error)
#   ("disk", path, nbytes, is_error)    <- spilled (reference local_object_manager.h:43)
#   ("remote", host_key, inner_loc)     <- lives on another host's node agent; only
#       the head's directory holds these (multi-host plane, reference
#       object_manager.h:119 cross-node transfer); workers always receive a
#       host-local location after the head localizes it
Location = Tuple

# ------------------------------------------------------------------- arena plumbing
_ARENA_ENV = "RAY_TPU_ARENA"
_arena_lock = threading.Lock()
_arenas: Dict[str, Any] = {}
_arena_default: Optional[Any] = None
_arena_disabled = False


def init_arena(capacity: int) -> Optional[str]:
    """Create this node's arena (coordinator side). Returns its name or None."""
    global _arena_default, _arena_disabled
    name = f"/rtpu_arena_{os.getpid()}_{os.urandom(3).hex()}"
    try:
        from ray_tpu._native.shm_store import Arena

        a = Arena.create(name, capacity)
    # graftlint: allow[swallowed-exception] degrades to the coded fallback (_arena_disabled = True) by design
    except Exception:
        _arena_disabled = True
        return None
    with _arena_lock:
        _arenas[name] = a
        _arena_default = a
    os.environ[_ARENA_ENV] = name  # driver-side materialize in this process
    return name


def destroy_arena() -> None:
    global _arena_default
    with _arena_lock:
        a = _arena_default
        _arena_default = None
    if a is not None and a.owner:
        try:
            a.unlink()
            a.close()
        # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
        except Exception:
            pass
        os.environ.pop(_ARENA_ENV, None)


def _open_arena(name: str):
    with _arena_lock:
        a = _arenas.get(name)
    if a is None:
        from ray_tpu._native.shm_store import Arena

        a = Arena.open(name)
        with _arena_lock:
            _arenas[name] = a
    return a


def _default_arena():
    """Writer-side arena: created locally (coordinator) or attached via env (workers)."""
    global _arena_default, _arena_disabled
    if _arena_default is None and not _arena_disabled:
        name = os.environ.get(_ARENA_ENV)
        if not name:
            return None
        try:
            _arena_default = _open_arena(name)
        # graftlint: allow[swallowed-exception] degrades to the coded fallback (_arena_disabled = True) by design
        except Exception:
            _arena_disabled = True
    return _arena_default


from .exceptions import ObjectLostError


class ObjectLost(ObjectLostError):
    pass


def materialize(obj: Any, oid: ObjectID, is_error: bool = False) -> Location:
    """Serialize obj and place it: small -> inline, large -> arena, overflow -> segment."""
    from ray_tpu.experimental import device_objects

    if not is_error and device_objects.is_device_array(obj):
        # same-process resolves return the original device array (no host copy);
        # cross-process consumers pull device-to-device via the transfer plane
        # when enabled (wrap_for_store), else use the serialized host copy
        device_objects.stash(oid.binary(), obj)
        obj = device_objects.wrap_for_store(oid.binary(), obj)
    ser = serialization.serialize(obj)
    size = ser.frame_bytes
    if size < _inline_threshold():
        return ("inline", ser.to_bytes(), is_error)
    arena = _default_arena()
    if arena is not None:
        buf = arena.create_object(oid.binary(), size)
        if buf is not None:
            try:
                ser.write_into(buf)
            finally:
                buf.release()
            arena.seal(oid.binary())
            if is_error:
                # recorded in the arena entry too, so a rebuilt directory
                # (head restart; agent re-reports contents) keeps raising it
                arena.set_flags(oid.binary(), 1)
            return ("arena", arena.name, oid.binary(), size, is_error)
    name = "rt_" + oid.hex()[:24]
    seg = shared_memory.SharedMemory(name=name, create=True, size=size)
    try:
        ser.write_into(seg.buf)
    finally:
        seg.close()
    return ("shm", name, size, is_error)


from ray_tpu.core.data_plane import PinnedRead


def read_pinned(loc: Location, offset: int = 0,
                length: Optional[int] = None) -> PinnedRead:
    """Zero-copy read: a PinnedRead whose view maps the object's frame bytes
    (or the clamped [offset, offset+length) range of them) STRAIGHT from the
    backing storage — no bytes materialized.

    The view is pinned against concurrent spill_lru/free_local for its
    lifetime: arena reads hold a C++ reader pin (delete defers the free to the
    last unpin, shm_store.cc kCondemned), shm/disk reads hold the mapping
    itself (unlink leaves live mappings valid; close defers while views are
    exported). Callers MUST release() — the data plane does so when the
    transfer ends, so a pull in flight can never observe torn bytes."""
    if offset < 0 or (length is not None and length < 0):
        raise ValueError(f"negative slice ({offset}, {length})")
    kind = loc[0]

    def clamp(size: int) -> Tuple[int, int]:
        end = size if length is None else min(offset + length, size)
        return min(offset, size), end

    if kind == "inline":
        _, frame, is_error = loc
        start, end = clamp(len(frame))
        return PinnedRead(memoryview(frame)[start:end], is_error)
    if kind == "arena":
        _, name, oid_bytes, size, is_error = loc
        arena = _open_arena(name)
        view = arena.get(oid_bytes)  # reader pin held until release()
        if view is None:
            raise ObjectLost(f"arena object {oid_bytes.hex()} was freed or lost")
        start, end = clamp(size)

        def unpin(v=view, a=arena, o=bytes(oid_bytes)):
            try:
                v.release()
            except BufferError:
                pass
            a.unpin(o)

        return PinnedRead(view[start:end], is_error, release=unpin)
    if kind == "shm":
        _, name, size, is_error = loc
        try:
            seg = _segment_cache.open(name)
        except FileNotFoundError:
            raise ObjectLost(f"shm segment {name} was freed or lost") from None
        start, end = clamp(size)
        # the exported view IS the pin: a concurrent drop()/unlink leaves this
        # mapping valid (close raises BufferError and the handle is parked)
        return PinnedRead(memoryview(seg.buf)[start:end], is_error)
    if kind == "disk":
        _, path, size, is_error = loc
        import mmap as _mmap

        try:
            f = open(path, "rb")
        except OSError:
            raise ObjectLost(f"spilled object file {path} was lost") from None
        try:
            try:
                m = _mmap.mmap(f.fileno(), 0, access=_mmap.ACCESS_READ)
            except (ValueError, OSError):
                raise ObjectLost(
                    f"spilled object file {path} was lost") from None
        finally:
            f.close()
        start, end = clamp(size)

        def close_map(mm=m):
            try:
                mm.close()
            except BufferError:
                pass

        return PinnedRead(memoryview(m)[start:end], is_error, release=close_map)
    raise ValueError(f"unknown location kind {kind!r}")


def read_pinned_any(loc: Location) -> PinnedRead:
    """Zero-copy data-plane read dispatcher (the read_fn node/agent DataServers
    serve with): a plain location pins the whole frame, a
    ``("slice", inner_loc, offset, length)`` wrapper pins only that byte range
    — striped pulls and ring steps fetch range k of a large object without the
    serving node copying anything out of shared memory."""
    if loc and loc[0] == "slice":
        _, inner, offset, length = loc
        return read_pinned(inner, int(offset), int(length))
    return read_pinned(loc)


def read_raw(loc: Location) -> Tuple[bytes, bool]:
    """Read an object's serialized frame bytes at a local location.

    Materializing fallback for paths that need an owned bytes object (head
    relay, agent fetch_object); the data plane itself streams read_pinned_any
    views without this copy. Returns (frame_bytes, is_error)."""
    if loc[0] == "inline":
        return loc[1], loc[2]
    with read_pinned(loc) as pr:
        return bytes(pr.view), pr.is_error


def read_raw_slice(loc: Location, offset: int, length: int) -> Tuple[bytes, bool]:
    """Read `length` bytes at `offset` of an object's serialized frame without
    materializing (or copying) the rest of the object. Out-of-range requests
    are clamped to the frame (a zero-length tail read returns b"")."""
    with read_pinned(loc, offset, length) as pr:
        return bytes(pr.view), pr.is_error


def read_raw_any(loc: Location) -> Tuple[bytes, bool]:
    """Materializing twin of read_pinned_any (legacy data-plane read fn)."""
    with read_pinned_any(loc) as pr:
        return bytes(pr.view), pr.is_error


def loc_meta(loc: Location) -> Tuple[Optional[int], bool]:
    """(frame_size, is_error) as recorded in a location tuple, without touching
    the bytes — (None, False) when the location doesn't carry a size. Pullers
    use the size to plan stripes BEFORE dialing and to pre-create the
    destination mapping."""
    kind = loc[0] if loc else None
    if kind == "inline":
        return len(loc[1]), loc[2]
    if kind == "arena":
        return loc[3], loc[4]
    if kind in ("shm", "disk"):
        return loc[2], loc[3]
    if kind == "slice":
        _, inner, offset, length = loc
        size, is_error = loc_meta(inner)
        if size is None:
            return None, is_error
        start = min(int(offset), size)
        return max(0, min(start + int(length), size) - start), is_error
    return None, False


def write_raw(data: bytes, oid: ObjectID, is_error: bool = False) -> Location:
    """Place already-serialized frame bytes locally (receiving side of a
    cross-host transfer): create_raw's allocation policy (arena first,
    per-object segment fallback), filled from an owned buffer and sealed."""
    tgt = create_raw(oid, len(data))
    try:
        tgt.view[:len(data)] = data
    except BaseException:
        tgt.abort()
        raise
    return tgt.seal(is_error)


class RawTarget:
    """A pre-created local destination for an incoming object's frame bytes.

    The receiving side of a zero-copy transfer: create_raw() allocates the
    final backing (arena slot / shm segment / small-object buffer) BEFORE any
    byte arrives, the data plane recv's chunk frames straight into `view`, and
    seal() publishes the location — the pulled object is never staged in an
    intermediate bytes object. abort() tears the allocation down if the
    transfer fails (arena delete defers to any late reader unpin)."""

    def __init__(self, kind: str, size: int, view: memoryview, *,
                 arena=None, oid_bytes: bytes = b"", seg=None, name: str = ""):
        self.kind = kind
        self.size = size
        self.view = view
        self._arena = arena
        self._oid_bytes = oid_bytes
        self._seg = seg
        self._name = name
        self._done = False

    def _release_view(self) -> None:
        try:
            self.view.release()
        except BufferError:
            pass

    def seal(self, is_error: bool = False) -> Location:
        if self._done:
            raise RuntimeError("RawTarget already sealed or aborted")
        self._done = True
        if self.kind == "inline":
            frame = bytes(self.view)
            self._release_view()
            return ("inline", frame, is_error)
        if self.kind == "arena":
            self._release_view()
            self._arena.seal(self._oid_bytes)
            if is_error:
                self._arena.set_flags(self._oid_bytes, 1)
            return ("arena", self._arena.name, self._oid_bytes, self.size,
                    is_error)
        self._release_view()
        try:
            self._seg.close()
        except BufferError:
            _unclosable_segments.append(self._seg)
        return ("shm", self._name, self.size, is_error)

    def abort(self) -> None:
        if self._done:
            return
        self._done = True
        self._release_view()
        if self.kind == "arena":
            try:
                self._arena.delete(self._oid_bytes)
            # graftlint: allow[swallowed-exception] arena slot already deleted by a racing free/spill; refcount owns correctness
            except Exception:
                pass
        elif self.kind == "shm":
            try:
                self._seg.close()
            except BufferError:
                _unclosable_segments.append(self._seg)
            # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
            except Exception:
                pass
            try:
                shared_memory.SharedMemory(name=self._name).unlink()
            # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
            except Exception:
                pass


def create_raw(oid: ObjectID, size: int) -> RawTarget:
    """Allocate the local backing an incoming frame of `size` bytes will land
    in (arena first, per-object segment fallback, plain buffer below the
    inline threshold) — the write side of write_raw, split out so transfers
    can fill it in place instead of handing over a finished bytes object."""
    if size < _inline_threshold():
        return RawTarget("inline", size, memoryview(bytearray(size)))
    arena = _default_arena()
    if arena is not None:
        buf = arena.create_object(oid.binary(), size)
        if buf is not None:
            return RawTarget("arena", size, buf, arena=arena,
                             oid_bytes=oid.binary())
    # randomized suffix: the source side's materialize() segment for this oid
    # may share this machine's /dev/shm namespace (same-host "multi-host" test
    # topology), so the deterministic name would collide
    name = "rt_" + oid.hex()[:16] + os.urandom(4).hex()
    seg = shared_memory.SharedMemory(name=name, create=True, size=size)
    return RawTarget("shm", size, memoryview(seg.buf)[:size], seg=seg, name=name)


def try_map_local(loc: Location) -> bool:
    """Probe whether `loc`'s backing storage is directly readable from THIS
    process — true exactly when the "remote" source shares this machine's
    shm/disk namespace (colocated node processes: head + agent on one host,
    the single-host pod test topology). The successful probe leaves the
    segment/arena handle cached, so later reads keep working even if the
    source node later unlinks the name. Names are oid-derived + random, so a
    cross-host name collision is not a practical concern."""
    try:
        pr = read_pinned(loc, 0, 0)
    except (ObjectLost, OSError, ValueError, KeyError):
        return False
    pr.release()
    return True


def pull_to_store(client, addr, loc: Location, oid: ObjectID) -> Location:
    """Destination side of a direct node-to-node transfer, zero-copy end to
    end: plan stripes from the location's recorded size, pre-create the local
    backing, land every chunk frame straight in it (DataClient recv-into), and
    seal in place. Replaces the pull-bytes-then-write_raw two-copy dance on the
    head and node-agent transfer routes.

    Fully zero-byte fast path: when the source location is readable in place
    (same-host topology, see try_map_local) the destination adopts it outright
    — the mapping is shared, nothing moves, matching the local get path's
    zero-copy semantics. Frees stay correct because both sides' free of the
    same segment/arena entry is idempotent and only fires at global refcount
    zero."""
    from ray_tpu.config import CONFIG
    from ray_tpu.util import telemetry

    if CONFIG.transfer_same_host_map and try_map_local(loc):
        size, _ = loc_meta(loc)
        telemetry.get_counter(
            "transfer_bytes_total", "object bytes pulled over the data plane",
            tag_keys=("path",)).inc(float(size or 0), tags={"path": "mapped"})
        telemetry.get_counter(
            "transfer_pulls_total", "completed data-plane pulls",
            tag_keys=("path",)).inc(1.0, tags={"path": "mapped"})
        if telemetry.enabled():
            telemetry.event("transfer.pull", "transfer",
                            bytes=int(size or 0), stripes=0, path="mapped",
                            gbps=0.0, admission_wait_ms=0.0)
        return loc
    size, _ = loc_meta(loc)
    cache: dict = {}

    def sink(total: int, is_error: bool) -> memoryview:
        tgt = cache.get("t")
        if tgt is not None:
            if tgt.size == total:
                return tgt.view  # retry attempt: overwrite in place
            tgt.abort()
        tgt = create_raw(oid, total)
        cache["t"] = tgt
        return tgt.view

    try:
        _, is_error = client.pull(addr, loc, into=sink, size_hint=size)
        return cache["t"].seal(is_error)
    except BaseException:
        tgt = cache.get("t")
        if tgt is not None:
            tgt.abort()
        raise


def free_local(loc: Location) -> None:
    """Physically delete a local (unwrapped) location's backing storage.

    Used by node agents when the head broadcasts a free for an object hosted
    on this agent's node."""
    kind = loc[0]
    if kind == "arena":
        try:
            _open_arena(loc[1]).delete(loc[2])
        # graftlint: allow[swallowed-exception] remote-free of a location its node may have already dropped
        except Exception:
            pass
    elif kind == "shm":
        name = loc[1]
        _segment_cache.drop(name)
        try:
            seg = shared_memory.SharedMemory(name=name)
            seg.close()
            seg.unlink()
        # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
        except Exception:
            pass
    elif kind == "disk":
        try:
            os.remove(loc[1])
        except OSError:
            pass


class _SegmentCache:
    """Per-process cache of opened read-side segments.

    Deserialized arrays are zero-copy views over the mapping, so segments stay mapped
    until the process exits or the coordinator broadcasts a free.
    """

    def __init__(self):
        self._segs: Dict[str, shared_memory.SharedMemory] = {}
        self._lock = threading.Lock()

    def open(self, name: str) -> shared_memory.SharedMemory:
        with self._lock:
            seg = self._segs.get(name)
            if seg is None:
                seg = shared_memory.SharedMemory(name=name)
                self._segs[name] = seg
            return seg

    def drop(self, name: str) -> None:
        with self._lock:
            seg = self._segs.pop(name, None)
        if seg is not None:
            try:
                seg.close()
            except BufferError:
                _unclosable_segments.append(seg)
            # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
            except Exception:
                pass


_segment_cache = _SegmentCache()
# segments whose mappings are pinned by live zero-copy views; kept referenced so
# SharedMemory.__del__ doesn't emit BufferError warnings during gc
_unclosable_segments: List[Any] = []


def resolve(loc: Location, oid: Optional[ObjectID] = None) -> Any:
    """Reconstruct the Python value at a location. Raises if it is an error object.

    When oid is given, a device-resident original in this process (jax.Array fast
    path, experimental/device_objects.py) is returned without deserializing."""
    if oid is not None:
        from ray_tpu.experimental import device_objects

        hit = device_objects.lookup(oid.binary())
        if hit is not None:
            return hit
    kind = loc[0]
    if kind == "inline":
        _, frame, is_error = loc
        value = serialization.loads(frame)
    elif kind == "arena":
        _, name, oid_bytes, size, is_error = loc
        arena = _open_arena(name)
        view = arena.get(oid_bytes)  # takes a reader pin
        if view is None:
            raise ObjectLost(f"arena object {oid_bytes.hex()} was freed or lost")
        value = serialization.deserialize_frame(view[:size])
        # Zero-copy views into the arena stay valid while the value lives: hold the
        # pin until the value is collected (plasma analog: client buffer refcount).
        # Roots that can't carry a finalizer (tuple/list/dict) get a private copy
        # instead, so the pin can drop immediately.
        try:
            import weakref

            weakref.finalize(value, arena.unpin, bytes(oid_bytes))
        except TypeError:
            copy = bytearray(view[:size])
            value = serialization.deserialize_frame(memoryview(copy))
            arena.unpin(oid_bytes)
    elif kind == "shm":
        _, name, size, is_error = loc
        try:
            seg = _segment_cache.open(name)
        except FileNotFoundError:
            raise ObjectLost(f"shm segment {name} was freed or lost") from None
        value = serialization.deserialize_frame(memoryview(seg.buf)[:size])
    elif kind == "disk":
        _, path, size, is_error = loc
        import mmap as _mmap

        try:
            with open(path, "rb") as f:
                m = _mmap.mmap(f.fileno(), 0, access=_mmap.ACCESS_READ)
        except (FileNotFoundError, ValueError, OSError):
            raise ObjectLost(f"spilled object file {path} was lost") from None
        # zero-copy: deserialized arrays are views over the file mapping; the
        # exported buffer keeps the mmap alive until the views are collected
        value = serialization.deserialize_frame(memoryview(m)[:size])
    else:
        raise ValueError(f"unknown location kind {kind!r}")
    if is_error:
        raise value
    return value


def _copy_to_disk(loc: Location, spill_dir: str) -> Optional[Location]:
    """Write a sealed arena/shm object's bytes to a disk file and return the
    file's location; the memory stays. None if the object cannot be spilled
    (inline/already-disk/lost)."""
    kind = loc[0]
    os.makedirs(spill_dir, exist_ok=True)
    if kind == "arena":
        _, name, oid_bytes, size, is_error = loc
        arena = _open_arena(name)
        view = arena.get(oid_bytes)  # reader pin
        if view is None:
            return None
        path = os.path.join(spill_dir, oid_bytes.hex())
        try:
            with open(path, "wb") as f:
                f.write(view[:size])
        finally:
            view.release()
            arena.unpin(oid_bytes)
        return ("disk", path, size, is_error)
    if kind == "shm":
        _, name, size, is_error = loc
        try:
            seg = shared_memory.SharedMemory(name=name)
        except FileNotFoundError:
            return None
        path = os.path.join(spill_dir, name)
        try:
            with open(path, "wb") as f:
                f.write(bytes(seg.buf[:size]))
        finally:
            try:
                seg.close()
            except BufferError:
                # zero-copy views in this process keep the mapping alive; park the
                # handle so its __del__ doesn't warn at gc time
                _unclosable_segments.append(seg)
        return ("disk", path, size, is_error)
    return None


def spill_location(loc: Location, spill_dir: str) -> Optional[Location]:
    """Move a sealed arena/shm object's bytes to a disk file, freeing the memory
    (reference LocalObjectManager::SpillObjects). Returns the new location, or
    None if the object cannot be spilled (inline/already-disk/lost)."""
    new_loc = _copy_to_disk(loc, spill_dir)
    if new_loc is not None:
        free_local(loc)  # shm: removes the name; live mappings elsewhere stay valid
    return new_loc


class ObjectStore:
    """Node-side coordinator: object directory, pending waits, refcounts, eviction."""

    def __init__(self):
        self._lock = threading.Lock()
        self._locations: Dict[ObjectID, Location] = {}  # insertion/touch order = LRU
        self._events: Dict[ObjectID, threading.Event] = {}
        self._refcounts: Dict[ObjectID, int] = {}
        self._failed: Dict[ObjectID, Exception] = {}
        self.on_free = None  # callback(oid) — cluster drops lineage entries
        # callback(loc) for ("remote", host, inner) locations — the cluster
        # forwards the free to the hosting node agent (multi-host plane)
        self.on_remote_free = None
        # callback(oid, old_loc) after spill_lru moves an object to disk:
        # adopted same-host-map replicas (pull_to_store shares the source's
        # mapping instead of copying) cache old_loc verbatim and must be
        # invalidated — the arena entry / segment name they point at is gone
        self.on_spill = None

    # -- directory -----------------------------------------------------------------
    def add(self, oid: ObjectID, loc: Location) -> None:
        with self._lock:
            self._locations[oid] = loc
            ev = self._events.pop(oid, None)
        if ev is not None:
            ev.set()

    def drop_location(self, oid: ObjectID) -> None:
        """Forget a lost location so lineage reconstruction can re-add it."""
        with self._lock:
            self._locations.pop(oid, None)

    def mark_failed(self, oid: ObjectID, err: Exception) -> None:
        with self._lock:
            self._failed[oid] = err
            ev = self._events.pop(oid, None)
        if ev is not None:
            ev.set()

    def contains(self, oid: ObjectID) -> bool:
        with self._lock:
            return oid in self._locations or oid in self._failed

    def location(self, oid: ObjectID, timeout: Optional[float] = None) -> Location:
        """Block until oid is available and return its location."""
        with self._lock:
            loc = self._locations.get(oid)
            if loc is not None:
                self._locations.pop(oid)  # LRU touch
                self._locations[oid] = loc
                return loc
            if oid in self._failed:
                raise self._failed[oid]
            ev = self._events.get(oid)
            if ev is None:
                ev = threading.Event()
                self._events[oid] = ev
        if not ev.wait(timeout):
            raise TimeoutError(f"timed out waiting for {oid!r}")
        with self._lock:
            if oid in self._failed:
                raise self._failed[oid]
            loc = self._locations[oid]
            # LRU touch for the spill policy
            self._locations.pop(oid)
            self._locations[oid] = loc
            return loc

    def try_location(self, oid: ObjectID) -> Optional[Location]:
        with self._lock:
            if oid in self._failed:
                raise self._failed[oid]
            return self._locations.get(oid)

    def wait(self, oids: List[ObjectID], num_returns: int, timeout: Optional[float]):
        """ray.wait semantics: first num_returns ready (by input order), rest not-ready."""
        import time

        deadline = None if timeout is None else time.monotonic() + timeout
        ready: List[ObjectID] = []
        pending = list(oids)
        while True:
            still_pending = []
            for oid in pending:
                with self._lock:
                    done = oid in self._locations or oid in self._failed
                if done:
                    ready.append(oid)
                else:
                    still_pending.append(oid)
            pending = still_pending
            if len(ready) >= num_returns or not pending:
                return ready, pending
            if deadline is not None and time.monotonic() >= deadline:
                return ready, pending
            time.sleep(0.001)

    # -- lifetime ------------------------------------------------------------------
    def incref(self, oid: ObjectID, n: int = 1) -> None:
        with self._lock:
            self._refcounts[oid] = self._refcounts.get(oid, 0) + n

    def decref(self, oid: ObjectID, n: int = 1) -> None:
        free = False
        with self._lock:
            c = self._refcounts.get(oid, 0) - n
            if c <= 0:
                self._refcounts.pop(oid, None)
                free = True
            else:
                self._refcounts[oid] = c
        if free:
            self._free(oid)

    def _free(self, oid: ObjectID) -> None:
        from ray_tpu.experimental import device_objects

        device_objects.drop(oid.binary())
        with self._lock:
            loc = self._locations.pop(oid, None)
            self._failed.pop(oid, None)
        if self.on_free is not None:
            try:
                self.on_free(oid)
            # graftlint: allow[swallowed-exception] GC/decref during teardown: the runtime may already be torn down
            except Exception:
                pass
        if loc is None:
            return
        if loc[0] == "remote":
            if self.on_remote_free is not None:
                try:
                    self.on_remote_free(loc)
                # graftlint: allow[swallowed-exception] GC/decref during teardown: the runtime may already be torn down
                except Exception:
                    pass
        else:
            free_local(loc)

    def spill_lru(self, bytes_to_free: int, spill_dir: str) -> int:
        """Spill least-recently-used arena/shm objects until bytes_to_free memory
        bytes are on disk (reference LocalObjectManager::SpillObjectsOfSize).
        Returns bytes actually spilled."""
        with self._lock:
            candidates = [
                (oid, loc) for oid, loc in self._locations.items()
                if loc[0] in ("arena", "shm")
            ]
        spilled = 0
        for oid, loc in candidates:  # dict order = LRU (oldest first)
            if spilled >= bytes_to_free:
                break
            try:
                new_loc = _copy_to_disk(loc, spill_dir)
            # graftlint: allow[swallowed-exception] callback isolation: a throwing subscriber must not break the caller
            except Exception:
                continue  # skip unspillable objects, keep relieving pressure
            if new_loc is None:
                continue
            # swap only if the object still lives at the snapshotted location:
            # a free() (refcount hit zero) or concurrent spill mid-write must not
            # leave an orphaned disk file counted as relieved memory
            with self._lock:
                swapped = self._locations.get(oid) == loc
                if swapped:
                    self._locations[oid] = new_loc
            # the memory goes only once the directory names the file: a reader
            # that resolved the old location and lost it asks again
            # (_recover_object) and must find the disk copy, not a dead entry
            free_local(loc if swapped else new_loc)
            if not swapped:
                continue
            if self.on_spill is not None:
                try:
                    self.on_spill(oid, loc)
                # graftlint: allow[swallowed-exception] callback isolation: a throwing subscriber must not break the caller
                except Exception:
                    pass
            spilled += new_loc[2]
        return spilled

    def memory_bytes(self) -> int:
        """Bytes resident in shared memory (arena + segments), i.e. spillable."""
        with self._lock:
            return sum(
                l[3] if l[0] == "arena" else l[2]
                for l in self._locations.values() if l[0] in ("arena", "shm")
            )

    def free_all(self) -> None:
        with self._lock:
            oids = list(self._locations.keys())
        for oid in oids:
            self._free(oid)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            shm_bytes = sum(l[2] for l in self._locations.values() if l[0] == "shm")
            arena_bytes = sum(l[3] for l in self._locations.values() if l[0] == "arena")
            inline_bytes = sum(len(l[1]) for l in self._locations.values() if l[0] == "inline")
            return {
                "num_objects": len(self._locations),
                "shm_bytes": shm_bytes,
                "arena_bytes": arena_bytes,
                "inline_bytes": inline_bytes,
            }
