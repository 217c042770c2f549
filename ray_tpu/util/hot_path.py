"""@hot_path / @control_path: latency-contract registries for graftlint.

Both decorators are runtime no-ops beyond recording the function in a
registry — their value is the CONTRACT they declare, which graftlint
enforces statically (`ray-tpu lint`):

- ``@hot_path`` marks a function on a device-rate loop (engine scheduler
  step, fused decode emit, grad-sync stage, ring-collective wait). The
  host-sync-in-hot-path check walks it plus its one-level same-file callees
  and flags device->host syncs (`.item()`, `np.asarray`, `float()` on
  arrays, `block_until_ready`) — the defect class behind the per-step decode
  round trip PR 12 had to dig out. A DESIGNED sync point (the one fetch per
  K-step burst) stays, with an inline
  ``# graftlint: allow[host-sync-in-hot-path] <why>``.

- ``@control_path`` marks a function the control plane depends on staying
  prompt (health probes, drain paths) that does NOT already ride a
  "control" actor concurrency group (those are picked up from the
  ``concurrency_group="control"`` declaration directly). The
  blocking-control-path check flags sleeps/object-fetches/socket reads
  inside.

Keep this module import-light: hot modules import it at module load.
"""
from __future__ import annotations

from typing import Callable, Optional, Set, TypeVar

F = TypeVar("F", bound=Callable)

HOT_PATHS: Set[str] = set()
CONTROL_PATHS: Set[str] = set()


def _register(registry: Set[str], fn: Callable) -> None:
    registry.add(f"{fn.__module__}:{fn.__qualname__}")


def hot_path(fn: Optional[F] = None, *, reason: str = "") -> F:
    """Declare a function hot: no host syncs inside (graftlint-enforced)."""
    del reason  # documentation at the decoration site, not used at runtime

    def deco(f: F) -> F:
        _register(HOT_PATHS, f)
        return f

    return deco(fn) if fn is not None else deco  # type: ignore[return-value]


def control_path(fn: Optional[F] = None, *, reason: str = "") -> F:
    """Declare a function control-plane: no blocking calls inside."""
    del reason

    def deco(f: F) -> F:
        _register(CONTROL_PATHS, f)
        return f

    return deco(fn) if fn is not None else deco  # type: ignore[return-value]
