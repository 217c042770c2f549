"""DataIterator: batch iteration with prefetch.

Capability parity: reference python/ray/data/iterator.py (iter_batches/iter_rows/
iter_torch_batches) + _internal/block_batching/. Prefetch pipelines object-store fetches
one block ahead of consumption — the pattern that keeps the TPU fed during training.
"""
from __future__ import annotations

import functools
import threading
import queue as _queue
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

import ray_tpu
from ray_tpu.util import telemetry

from .block import BlockAccessor


def _in_data_lap(iterate):
    """A generator method whose every item is asked for inside the `train.loop.data` lap
    of the calling thread's train loop (train/session.py), where that thread runs one:
    from the moment the loop asks for a batch until it has it."""
    @functools.wraps(iterate)
    def lapped(*args, **kwargs):
        items = iterate(*args, **kwargs)
        while True:
            with telemetry.lap(telemetry.DATA_LAP):
                try:
                    item = next(items)
                except StopIteration:
                    return
            yield item

    return lapped


class DataIterator:
    """Iterates batches over (block_ref, metadata) bundles — a materialized list
    OR a live execute_iter() generator, in which case batches yield while
    upstream operators are still producing (reference iter_batches streaming)."""

    def __init__(self, bundles: Any):
        self._bundles = bundles
        self._consumed = False

    def _iter_blocks(self, prefetch_blocks: int = 1):
        if self._consumed and not isinstance(self._bundles, (list, tuple)):
            raise RuntimeError(
                "this DataIterator streams a live execution and was already "
                "consumed; call Dataset.iterator() again (re-executes) or "
                "Dataset.materialize() first for multi-epoch iteration")
        self._consumed = True
        q: _queue.Queue = _queue.Queue(maxsize=max(1, prefetch_blocks))
        SENTINEL = object()
        stop = threading.Event()

        def offer(item) -> bool:
            """put() that gives up when the consumer abandoned us."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except _queue.Full:
                    continue
            return False

        def producer():
            try:
                for r, _ in self._bundles:
                    if not offer(ray_tpu.get(r)):
                        break
                else:
                    offer(SENTINEL)
            except BaseException as e:  # noqa: BLE001 - re-raised in the consumer
                offer(e)
            finally:
                # ALWAYS close the live execution generator HERE (this thread is
                # its only driver) so every stage's finally runs — actor pools
                # killed, stats recorded. Covers early consumer abandonment AND
                # a mid-stream task failure; a no-op on exhausted generators.
                close = getattr(self._bundles, "close", None)
                if close is not None:
                    try:
                        close()
                    # graftlint: allow[swallowed-exception] best-effort cleanup of a target that may already be dead/gone
                    except Exception:
                        pass

        t = threading.Thread(target=producer, daemon=True,
                             name="data-iter-producer")
        t.start()
        try:
            while True:
                item = q.get()
                if item is SENTINEL:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            try:
                q.get_nowait()  # wake a producer blocked mid-put
            except _queue.Empty:
                pass

    @_in_data_lap
    def iter_batches(
        self,
        *,
        batch_size: Optional[int] = 256,
        batch_format: str = "numpy",
        drop_last: bool = False,
        local_shuffle_buffer_size: Optional[int] = None,
        local_shuffle_seed: Optional[int] = None,
        prefetch_blocks: int = 1,
    ) -> Iterator[Any]:
        carry = None  # leftover rows spanning block boundaries (arrow table)
        rng = np.random.default_rng(local_shuffle_seed)
        for block in self._iter_blocks(prefetch_blocks):
            if carry is not None and carry.num_rows:
                block = BlockAccessor.concat([carry, block])
                carry = None
            acc = BlockAccessor.for_block(block)
            n = acc.num_rows()
            if batch_size is None:
                yield acc.to_batch_format(batch_format)
                continue
            if local_shuffle_buffer_size and n:
                perm = rng.permutation(n)
                block = acc.take(perm)
                acc = BlockAccessor.for_block(block)
            start = 0
            while n - start >= batch_size:
                yield BlockAccessor.for_block(acc.slice(start, start + batch_size)).to_batch_format(batch_format)
                start += batch_size
            if start < n:
                carry = acc.slice(start, n)
        if carry is not None and carry.num_rows and not drop_last and batch_size is not None:
            yield BlockAccessor.for_block(carry).to_batch_format(batch_format)

    @_in_data_lap
    def iter_rows(self) -> Iterator[Dict[str, Any]]:
        for block in self._iter_blocks():
            yield from BlockAccessor.for_block(block).iter_rows()

    @_in_data_lap
    def iter_torch_batches(self, *, batch_size: Optional[int] = 256, **kw) -> Iterator[Dict[str, Any]]:
        import torch

        for batch in self.iter_batches(batch_size=batch_size, batch_format="numpy", **kw):
            yield {k: torch.as_tensor(v) for k, v in batch.items() if v.dtype != object}

    @_in_data_lap
    def iter_jax_batches(
        self, *, batch_size: Optional[int] = 256, sharding=None, **kw
    ) -> Iterator[Dict[str, Any]]:
        """TPU-native: yield device-resident jax.Arrays, optionally pre-sharded.

        With a NamedSharding, each batch lands distributed across the mesh without a
        host-side gather — the iter path the JaxTrainer uses for data-parallel ingest.
        """
        import jax

        for batch in self.iter_batches(batch_size=batch_size, batch_format="numpy", **kw):
            arrs = {k: v for k, v in batch.items() if v.dtype != object}
            if sharding is not None:
                yield {k: jax.device_put(v, sharding) for k, v in arrs.items()}
            else:
                yield {k: jax.device_put(v) for k, v in arrs.items()}
