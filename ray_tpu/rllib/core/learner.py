"""Learner / JaxLearner: the gradient-update unit.

Capability parity: reference rllib/core/learner/learner.py:108 (compute_losses :893,
update :978) and torch/torch_learner.py:67. TPU-first: instead of torch autograd + DDP
wrapping (torch_learner.py:523), the update is one jitted jax.value_and_grad step with
optax; multi-learner gradient sync is an allreduce over the ray_tpu collective group
(ICI/XLA analog of the reference's NCCL allreduce).
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .rl_module import Columns, RLModuleSpec


from ray_tpu.util import telemetry
from ray_tpu.util.collective import CollectiveActorMixin
from ray_tpu.util.hot_path import hot_path


class Learner(CollectiveActorMixin):
    """Owns one RLModule's params + optimizer; subclass defines the loss."""

    def __init__(self, config: "AlgorithmConfig", module_spec: RLModuleSpec):  # noqa: F821
        self.config = config
        self.module_spec = module_spec
        self.module = module_spec.build()
        self._group_name: Optional[str] = None
        self.metrics: Dict[str, Any] = {}

    def build(self) -> None:
        import optax

        # params/opt_state stay DEVICE-RESIDENT between updates: fetching them
        # to host every update() (and re-uploading every minibatch) costs more
        # than the update itself on real accelerators.
        # get_weights/get_state materialize numpy on demand.
        self.params = self.module.init_params(seed=self.config.seed or 0)
        clip = self.config.grad_clip
        tx = [optax.clip_by_global_norm(clip)] if clip else []
        tx.append(optax.adam(self.config.lr))
        self.optimizer = optax.chain(*tx)
        self.opt_state = self.optimizer.init(self.params)
        self._update_fn = self._build_update_fn()
        self._fused_update_fn = self._build_fused_update_fn()
        self._gather_update_fn = self._build_gather_update_fn()
        self._prepare_fn = None
        self._plane = None
        self._weights_version = 0

    # -- to be provided by algo-specific learners ------------------------------
    def compute_losses(self, params, batch: Dict[str, Any]):
        """Return (total_loss, aux_metrics_dict) as jax scalars."""
        raise NotImplementedError

    @staticmethod
    def _cast_obs(batch):
        """Cast OBS to f32 at the minibatch level, inside jit. Trajectory
        blocks carry obs in the env's native dtype (uint8 atari frames) all
        the way to the minibatch step — casting a 128-row gather is free,
        materializing the full block as f32 is 4x the memory traffic. On an
        already-f32 batch (the serialized path) the cast is a no-op."""
        import jax.numpy as jnp

        if Columns.OBS in batch:
            batch = dict(batch)
            batch[Columns.OBS] = batch[Columns.OBS].astype(jnp.float32)
        return batch

    def _build_update_fn(self):
        import jax

        def loss_fn(params, batch):
            loss, aux = self.compute_losses(params, batch)
            return loss, aux

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        @jax.jit
        def update(params, batch):
            (loss, aux), grads = grad_fn(params, self._cast_obs(batch))
            return loss, aux, grads

        return update

    def _build_fused_update_fn(self):
        """Single-learner fast path: loss -> grads -> optax -> new params in
        ONE jitted program (one device dispatch per minibatch). Multi-learner
        keeps the split path so the grad allreduce can run between."""
        import jax
        import optax

        def loss_fn(params, batch):
            loss, aux = self.compute_losses(params, batch)
            return loss, aux

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        @jax.jit
        def step(params, opt_state, batch):
            (loss, aux), grads = grad_fn(params, self._cast_obs(batch))
            updates, opt_state = self.optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss, aux

        return step

    def _build_gather_update_fn(self):
        """Device-resident minibatch SGD: the batch is uploaded ONCE per
        update and the ENTIRE epoch schedule — every epoch's permuted
        [steps, mb] index matrix — runs as one jitted lax.scan with
        (params, opt_state) as carry and on-device gathers (`v[ix]`). One
        device dispatch per update() replaces the serialized path's host
        re-slice + re-upload (and re-dispatch) of every single minibatch."""
        import jax
        import optax

        def loss_fn(params, batch):
            loss, aux = self.compute_losses(params, batch)
            return loss, aux

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        @jax.jit
        def epochs(params, opt_state, batch, idx):
            def step(carry, ix):
                params, opt_state = carry
                mbatch = self._cast_obs({k: v[ix] for k, v in batch.items()})
                (loss, aux), grads = grad_fn(params, mbatch)
                updates, opt_state = self.optimizer.update(
                    grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), (loss, aux)

            (params, opt_state), (losses, auxs) = jax.lax.scan(
                step, (params, opt_state), idx)
            return params, opt_state, losses, auxs

        return epochs

    # -- collective group (multi-learner DDP analog) ---------------------------
    def setup_collective(self, group_name: str) -> None:
        self._group_name = group_name

    def _sync_grads(self, grads):
        if self._group_name is None:
            return grads
        import jax

        from ray_tpu.util import collective as col

        leaves, treedef = jax.tree_util.tree_flatten(grads)
        # graftlint: allow[host-sync-in-hot-path] host-plane shm allreduce: grads must land on host to ride the collective
        flat = np.concatenate([np.asarray(l).ravel() for l in leaves])
        reduced = col.allreduce(flat, group_name=self._group_name)
        reduced = reduced / col.get_collective_group_size(self._group_name)
        out, off = [], 0
        for l in leaves:
            n = int(np.prod(np.shape(l)))
            # graftlint: allow[host-sync-in-hot-path] reduced grads are host arrays by construction (shm backend)
            out.append(np.asarray(reduced[off : off + n]).reshape(np.shape(l)))
            off += n
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- update ---------------------------------------------------------------
    def update(self, batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """One pass of minibatch SGD epochs over the batch (learner.py:_update)."""
        n = len(batch[Columns.OBS])
        arrays = {k: v for k, v in batch.items()
                  if isinstance(v, np.ndarray) and len(v) == n}
        return self._minibatch_sgd(arrays, n)

    @hot_path(reason="the learner inner loop: one device dispatch per minibatch")
    def _minibatch_sgd(self, arrays: Dict[str, Any], n: int) -> Dict[str, Any]:
        """Minibatch SGD epochs over columns of length n (numpy or device).

        Default path uploads the batch to device ONCE and gathers each
        minibatch on device (`_gather_update_fn`); the legacy host-slicing
        path (re-slice + re-upload per minibatch) stays selectable via
        RAY_TPU_RL_HOST_SLICING for the `serialized_opt` bench row, and is
        still used by the multi-learner group path whose grad allreduce runs
        on host between the split halves of the step.
        """
        import jax

        mb = min(self.config.minibatch_size or n, n)
        epochs = self.config.num_epochs
        rng = np.random.default_rng(0)
        host_slicing = (self._group_name is not None
                        or os.environ.get("RAY_TPU_RL_HOST_SLICING", "0") == "1")
        if not host_slicing:
            arrays = {k: jax.device_put(v) for k, v in arrays.items()}
            # full minibatches only (constant shapes keep one jit trace),
            # every epoch's permutation stacked into one [steps, mb] matrix:
            # the whole SGD schedule is a single device dispatch
            idx = np.stack([
                rng.permutation(n)[: (n // mb) * mb].reshape(-1, mb)
                for _ in range(epochs)]).reshape(-1, mb).astype(np.int32)
            self.params, self.opt_state, losses, auxs = self._gather_update_fn(
                self.params, self.opt_state, arrays, idx)
            # ONE host sync for the whole update, after every minibatch ran
            self.metrics = {
                "total_loss": float(np.mean(np.asarray(losses))),  # graftlint: allow[host-sync-in-hot-path] single designed metrics fetch after the fused epoch scan
                **{k: float(np.asarray(v)[-1]) for k, v in auxs.items()},  # graftlint: allow[host-sync-in-hot-path] same designed metrics boundary
                "minibatch_steps": int(idx.shape[0]),
            }
            return self.metrics
        losses, aux_out = [], {}
        steps = 0
        arrays = {k: np.asarray(v) for k, v in arrays.items()}  # graftlint: allow[host-sync-in-hot-path] legacy/group path materializes the batch on host by design
        for _ in range(epochs):
            perm = rng.permutation(n)
            # full minibatches only: constant shapes keep one jit trace
            for start in range(0, n - mb + 1, mb):
                idx = perm[start : start + mb]
                if self._group_name is not None:
                    mbatch = {k: v[idx] for k, v in arrays.items()}
                    loss, aux, grads = self._update_fn(self.params, mbatch)
                    grads = self._sync_grads(grads)
                    updates, self.opt_state = self.optimizer.update(
                        grads, self.opt_state, self.params)
                    import optax

                    self.params = optax.apply_updates(self.params, updates)
                else:
                    mbatch = {k: v[idx] for k, v in arrays.items()}
                    self.params, self.opt_state, loss, aux = self._fused_update_fn(
                        self.params, self.opt_state, mbatch)
                losses.append(loss)
                aux_out = aux
                steps += 1
        # ONE host sync for the whole update, after every minibatch dispatched
        self.metrics = {
            "total_loss": float(np.mean([float(l) for l in losses])),  # graftlint: allow[host-sync-in-hot-path] single designed metrics fetch after all minibatches dispatched
            **{k: float(v) for k, v in aux_out.items()},  # graftlint: allow[host-sync-in-hot-path] same designed metrics boundary
            "minibatch_steps": steps,
        }
        return self.metrics

    # -- decoupled rollout-plane path ------------------------------------------
    def setup_decoupled(self, authkey: bytes, publisher: bool = False,
                        start_version: int = 0) -> None:
        """Join the rollout plane's zero-copy transport (block pulls in,
        versioned weight broadcasts out if this rank is the publisher).
        `start_version` preserves broadcast-version monotonicity when a
        restarted group re-attaches."""
        from ray_tpu.util.collective import ring

        self._plane = ring.get_plane(authkey, min_streams=2)
        self._is_publisher = bool(publisher)
        self._weights_version = int(start_version)

    def publish_weights(self) -> Tuple[int, Tuple[str, int], int]:
        """Publish current params as `rlwts:<version>` on this learner's data
        plane; keeps the previous version alive so a worker mid-pull never
        races a retract. Returns (version, addr, nbytes) for the mailbox."""
        from ..rollout_plane import pack_params

        self._weights_version += 1
        data = pack_params(self.params)
        self._plane.publish(f"rlwts:{self._weights_version}", data,
                            expected_read_bytes=0)
        stale = self._weights_version - 2
        if stale > 0:
            self._plane.retract(f"rlwts:{stale}")
        return (self._weights_version, tuple(self._plane.addr), len(data))

    def _build_prepare_fn(self):
        """Jitted block → train-batch transform: advantage pass ON DEVICE
        (gae_scan / V-trace over the block time axis) + masked batch-wide
        advantage standardization, replacing the host-numpy connector."""
        import jax
        import jax.numpy as jnp

        from ..utils.gae import gae_scan, vtrace_block

        cfg = self.config
        gamma = cfg.gamma
        lam = float(getattr(cfg, "lambda_", 0.95))
        correction = getattr(cfg, "correction", "is_clip")
        rho_thr = float(getattr(cfg, "vtrace_clip_rho_threshold", 1.0))
        pg_rho_thr = float(getattr(cfg, "vtrace_clip_pg_rho_threshold", 1.0))

        def standardize(adv, mask):
            msum = jnp.maximum(mask.sum(), 1.0)
            mean = (adv * mask).sum() / msum
            var = (((adv - mean) ** 2) * mask).sum() / msum
            return (adv - mean) / jnp.maximum(jnp.sqrt(var), 1e-6)

        if correction == "vtrace":

            @jax.jit
            def prepare(params, obs, actions, action_logp, rewards, vf_preds,
                        boot_values, terminated, truncated, valid):
                Tp1, B = obs.shape[0], obs.shape[1]
                T = Tp1 - 1
                # keep obs in the env's native dtype (uint8 frames stay
                # 1 B/px); the minibatch step casts its gathers (_cast_obs)
                obs_flat = obs.reshape(Tp1 * B, -1)
                term = terminated.astype(jnp.float32)
                trunc = truncated.astype(jnp.float32)
                mask = valid.astype(jnp.float32)
                rewards_f = rewards.astype(jnp.float32)
                out = self.module.forward_train(
                    params, {Columns.OBS: obs_flat.astype(jnp.float32)})
                values_ext = out[Columns.VF_PREDS].reshape(Tp1, B)
                dist = self.module.action_dist_cls
                logits = out[Columns.ACTION_DIST_INPUTS][: T * B]
                act_flat = actions.reshape((T * B,) + actions.shape[2:])
                target_logp = dist.logp_jax(logits, act_flat).reshape(T, B)
                rhos = jnp.exp(target_logp - action_logp) * mask
                adv, targets = vtrace_block(
                    rewards_f, values_ext[:T], values_ext[1:], term, trunc,
                    rhos, gamma=gamma, lambda_=1.0,
                    clip_rho_threshold=rho_thr,
                    clip_pg_rho_threshold=pg_rho_thr)
                adv = standardize(adv, mask)

                def flat(x):
                    return x.reshape((T * B,) + x.shape[2:])

                return {
                    Columns.OBS: obs_flat[: T * B],
                    Columns.ACTIONS: flat(actions),
                    Columns.ACTION_LOGP: flat(action_logp),
                    Columns.ADVANTAGES: flat(adv),
                    Columns.VALUE_TARGETS: flat(targets),
                    "loss_mask": flat(mask),
                }

            return prepare

        # "is_clip": GAE off behaviour values; PPO's ratio clip is the IS
        # correction. The advantage pass never touches obs, so the 50+ MB
        # obs block stays OUT of this program entirely — the caller attaches
        # it as a host view and the minibatch step uploads it once.
        @jax.jit
        def prepare(actions, action_logp, rewards, vf_preds,
                    boot_values, terminated, truncated, valid):
            T, B = actions.shape[0], actions.shape[1]
            term = terminated.astype(jnp.float32)
            trunc = truncated.astype(jnp.float32)
            mask = valid.astype(jnp.float32)
            rewards_f = rewards.astype(jnp.float32)
            adv, targets = gae_scan(
                rewards_f, vf_preds, boot_values, term, trunc,
                gamma=gamma, lambda_=lam)
            adv = standardize(adv, mask)

            def flat(x):
                return x.reshape((T * B,) + x.shape[2:])

            return {
                Columns.ACTIONS: flat(actions),
                Columns.ACTION_LOGP: flat(action_logp),
                Columns.ADVANTAGES: flat(adv),
                Columns.VALUE_TARGETS: flat(targets),
                "loss_mask": flat(mask),
            }

        return prepare

    def update_from_blocks(self, handles: List[Any]) -> Dict[str, Any]:
        """Decoupled update: land trajectory blocks (mapped adoption or
        striped pull), run the advantage pass inside the jitted program, and
        do minibatch SGD with on-device gathers. Returns metrics plus the
        fresh weights broadcast descriptor when this rank publishes."""
        from ..rollout_plane import read_block_arrays

        with telemetry.span("rl.learner_update", "rl", blocks=len(handles)):
            # single-block rounds adopt the mapped obs zero-copy; the pin is
            # released below once the SGD pass (whose end-of-update metrics
            # fetch synchronizes the device) has consumed it
            blocks = [read_block_arrays(h, self._plane, adopt=len(handles) == 1)
                      for h in handles]
            pins = [b.pop("_pin") for b in blocks if "_pin" in b]
            try:
                return self._update_from_fields(blocks, handles)
            finally:
                for p in pins:
                    p.release()

    def _update_from_fields(self, blocks, handles) -> Dict[str, Any]:
        if len(blocks) > 1:
            fields = {k: np.concatenate([b[k] for b in blocks], axis=1)
                      for k in blocks[0]}
        else:
            fields = blocks[0]
        if self._prepare_fn is None:
            self._prepare_fn = self._build_prepare_fn()
        if getattr(self.config, "correction", "is_clip") == "vtrace":
            batch = dict(self._prepare_fn(
                self.params, fields["obs"], fields["actions"],
                fields["action_logp"], fields["rewards"],
                fields["vf_preds"], fields["boot_values"],
                fields["terminated"], fields["truncated"],
                fields["valid"]))
        else:
            batch = dict(self._prepare_fn(
                fields["actions"], fields["action_logp"],
                fields["rewards"], fields["vf_preds"],
                fields["boot_values"], fields["terminated"],
                fields["truncated"], fields["valid"]))
            # native-dtype obs rides along as a zero-copy host VIEW of
            # the pinned block ([T*B] prefix); the minibatch step's
            # device_put uploads it once per update
            T, B = fields["actions"].shape[:2]
            batch[Columns.OBS] = fields["obs"][:T].reshape(T * B, -1)
        n = batch[Columns.ACTIONS].shape[0]
        metrics = self._minibatch_sgd(batch, n)
        telemetry.get_counter("rl_learner_updates_total").inc()
        metrics["env_steps"] = int(sum(h.env_steps for h in handles))
        return metrics

    # -- state ----------------------------------------------------------------
    def _host_params(self):
        import jax

        return jax.tree_util.tree_map(lambda a: np.asarray(a), self.params)

    def get_state(self) -> Dict[str, Any]:
        import jax

        return {"params": self._host_params(),
                "opt_state": jax.tree_util.tree_map(lambda a: np.asarray(a),
                                                    self.opt_state)}

    def set_state(self, state: Dict[str, Any]) -> None:
        self.params = state["params"]
        if state.get("opt_state") is not None:
            self.opt_state = state["opt_state"]

    def get_weights(self):
        return self._host_params()

    def ping(self) -> bool:
        return True
