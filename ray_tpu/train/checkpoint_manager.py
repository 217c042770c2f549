"""Checkpoint persistence + top-k retention.

Reference capability: python/ray/train/_internal/checkpoint_manager.py and
_internal/storage.py (StorageContext). Worker-reported checkpoints are moved into the run
storage directory as checkpoint_{:06d}; retention ordered by CheckpointConfig's score
attribute (ties/no-score: recency).
"""
from __future__ import annotations

import os
import shutil
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ..air.config import CheckpointConfig
from . import storage
from .checkpoint import Checkpoint


@dataclass
class _TrackedCheckpoint:
    checkpoint: Checkpoint
    index: int
    metrics: Dict[str, Any] = field(default_factory=dict)


class CheckpointManager:
    def __init__(self, storage_dir: str, config: Optional[CheckpointConfig] = None):
        storage_dir = storage.normalize(storage_dir)
        self._remote = storage.is_remote(storage_dir)
        self.storage_dir = storage_dir if self._remote else os.path.abspath(storage_dir)
        if not self._remote:
            os.makedirs(self.storage_dir, exist_ok=True)
        self.config = config or CheckpointConfig()
        self._tracked: List[_TrackedCheckpoint] = []
        self._next_index = 0
        # What the running attempt's workers were started from. Reports are no
        # barrier: a rank still downloading it must not lose it to the
        # retention of the checkpoints a faster rank has reported since.
        self.resume_point: Optional[Checkpoint] = None
        # Rerunning with the same RunConfig.name must continue the index sequence, not
        # collide with (and nest inside) existing checkpoint_NNNNNN directories.
        for entry in sorted(storage.listdir(self.storage_dir) if self._remote
                            else os.listdir(self.storage_dir)):
            if not entry.startswith("checkpoint_"):
                continue
            path = self._join(entry)
            if not self._remote and not os.path.isdir(path):
                continue
            ckpt = Checkpoint(path)
            meta = ckpt.get_metadata()
            idx = meta.get("index", int(entry.split("_")[1]))
            self._tracked.append(_TrackedCheckpoint(ckpt, idx, meta.get("metrics", {})))
            self._next_index = max(self._next_index, idx + 1)

    def _join(self, *parts: str) -> str:
        return storage.join_any(self.storage_dir, *parts)

    @property
    def staging_dir(self) -> str:
        """Where worker sessions stage checkpoints before registration. Local
        runs: a dir on the run's filesystem (zero-copy move). Remote runs: a
        URI under the run — workers UPLOAD there (reference storage.py:358
        persist_to_storage), so no shared disk is ever assumed."""
        return self._join(".staging")

    def register(self, checkpoint: Checkpoint, metrics: Dict[str, Any]) -> Checkpoint:
        """Persist a worker-reported checkpoint into run storage; returns the durable one."""
        idx = self._next_index
        self._next_index += 1
        dest = self._join(f"checkpoint_{idx:06d}")
        storage.persist_dir(checkpoint.path, dest)
        durable = Checkpoint(dest)
        durable.update_metadata({"index": idx, "metrics": {k: _jsonable(v) for k, v in metrics.items()}})
        self._tracked.append(_TrackedCheckpoint(durable, idx, metrics))
        self._enforce_retention()
        return durable

    def _score(self, t: _TrackedCheckpoint):
        attr = self.config.checkpoint_score_attribute
        if attr is None:
            return t.index
        v = t.metrics.get(attr)
        if v is None:
            return float("-inf") if self.config.checkpoint_score_order == "max" else float("inf")
        return v

    def _enforce_retention(self) -> None:
        k = self.config.num_to_keep
        if k is None or len(self._tracked) <= k:
            return
        reverse = self.config.checkpoint_score_order == "max"
        ranked = sorted(self._tracked, key=self._score, reverse=reverse)
        keep = set(id(t) for t in ranked[:k])
        # Never delete the most recent checkpoint — it's the resume point.
        latest = max(self._tracked, key=lambda t: t.index)
        keep.add(id(latest))
        if self.resume_point is not None:
            keep.update(id(t) for t in self._tracked
                        if t.checkpoint.path == self.resume_point.path)
        survivors = []
        for t in self._tracked:
            if id(t) in keep:
                survivors.append(t)
            elif t.checkpoint.is_remote:
                storage.delete(t.checkpoint.path)
            else:
                shutil.rmtree(t.checkpoint.path, ignore_errors=True)
        self._tracked = survivors

    @property
    def latest_checkpoint(self) -> Optional[Checkpoint]:
        if not self._tracked:
            return None
        return max(self._tracked, key=lambda t: t.index).checkpoint

    @property
    def best_checkpoint(self) -> Optional[Checkpoint]:
        if not self._tracked:
            return None
        reverse = self.config.checkpoint_score_order == "max"
        return sorted(self._tracked, key=self._score, reverse=reverse)[0].checkpoint

    def list(self) -> List[Checkpoint]:
        return [t.checkpoint for t in sorted(self._tracked, key=lambda t: t.index)]


def _jsonable(v):
    try:
        import json

        json.dumps(v)
        return v
    except (TypeError, ValueError):
        return repr(v)
