"""RLlib + Data benchmarks: the two north-star workloads without committed numbers
until round 4.

- PPO CartPole: env-steps/s sampled + learner minibatch updates/s, the
  reference's rllib/benchmarks/ppo shape (benchmark_ppo_mujoco.py measures the
  same two rates).
- PPO on a synthetic Atari-shaped env (84x84x4 uint8 obs, Discrete(6)): stresses
  observation transport rollout -> GAE -> learner at Atari payload sizes without
  needing ALE (reference rllib/tuned_examples/ppo/atari_ppo.py geometry).
- Data: rows/s through a two-stage map_batches batch-inference pipeline on the
  pull-based streaming executor (reference release/nightly_tests/dataset/).

Writes RL_BENCH.json. Runs on the CPU sandbox: absolute rates are bounded by the
4-CPU worker pool and Python env stepping, not by the framework's data paths —
the numbers exist to make regressions visible and to prove the pipelines run at
realistic payload sizes.

Run: python bench_rllib.py [--quick]
"""
import json
import os
import sys
import time

import numpy as np

QUICK = "--quick" in sys.argv


class SyntheticAtariEnv:
    """Atari-shaped observations at CartPole cost: random uint8 frames stamped
    from a pre-generated bank, fixed-length episodes, dense random reward."""

    metadata = {"render_modes": []}
    render_mode = None
    spec = None

    def __init__(self, config=None):
        import gymnasium as gym

        config = config or {}
        self.observation_space = gym.spaces.Box(0, 255, (84, 84, 4), np.uint8)
        self.action_space = gym.spaces.Discrete(6)
        self.ep_len = int(config.get("ep_len", 200))
        self._bank = np.random.default_rng(0).integers(
            0, 255, size=(16, 84, 84, 4), dtype=np.uint8)
        self._t = 0

    def reset(self, *, seed=None, options=None):
        self._t = 0
        return self._bank[0], {}

    def step(self, action):
        self._t += 1
        obs = self._bank[self._t % len(self._bank)]
        done = self._t >= self.ep_len
        return obs, float(action == 1), done, False, {}

    def close(self):
        pass

    @classmethod
    def make_vec(cls, num_envs, config=None):
        return SyntheticAtariVectorEnv(num_envs, config)


class SyntheticAtariVectorEnv:
    """Natively-vectorized SyntheticAtariEnv: one numpy-batched step for all
    envs instead of gymnasium SyncVectorEnv's per-env Python loop. Semantics
    match SyncVectorEnv over SyntheticAtariEnv exactly, including gymnasium
    1.x next-step autoreset (a done env's next step ignores the action and
    returns the new episode's first obs with zero reward)."""

    def __init__(self, num_envs, config=None):
        import gymnasium as gym

        config = config or {}
        self.num_envs = int(num_envs)
        self.single_observation_space = gym.spaces.Box(0, 255, (84, 84, 4), np.uint8)
        self.single_action_space = gym.spaces.Discrete(6)
        self.ep_len = int(config.get("ep_len", 200))
        self._bank = np.random.default_rng(0).integers(
            0, 255, size=(16, 84, 84, 4), dtype=np.uint8)
        self._t = np.zeros(self.num_envs, dtype=np.int64)
        self._needs_reset = np.zeros(self.num_envs, dtype=bool)

    def reset(self, *, seed=None, options=None):
        self._t[:] = 0
        self._needs_reset[:] = False
        return np.broadcast_to(
            self._bank[0], (self.num_envs,) + self._bank.shape[1:]).copy(), {}

    def step(self, actions):
        actions = np.asarray(actions)
        resetting = self._needs_reset
        self._t = np.where(resetting, 0, self._t + 1)
        obs = self._bank[self._t % len(self._bank)]
        rewards = np.where(resetting, 0.0, (actions == 1).astype(np.float64))
        term = np.where(resetting, False, self._t >= self.ep_len)
        trunc = np.zeros(self.num_envs, dtype=bool)
        self._needs_reset = term.copy()
        return obs, rewards, term, trunc, {}

    def close(self):
        pass


def bench_ppo(env, name, *, train_batch, minibatch, epochs, iters, model_config=None):
    from ray_tpu.rllib.algorithms.ppo import PPOConfig

    cfg = (
        PPOConfig()
        .environment(env)
        .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                     rollout_fragment_length=64)
        .training(lr=3e-4, train_batch_size=train_batch, minibatch_size=minibatch,
                  num_epochs=epochs, gamma=0.99, lambda_=0.95, clip_param=0.3,
                  entropy_coeff=0.01)
        .debugging(seed=0)
    )
    if model_config:
        cfg.rl_module(model_config=model_config)
    algo = cfg.build_algo()
    try:
        algo.train()  # warmup: jit compiles, env resets — excluded from timing
        t0 = time.perf_counter()
        returns = []
        for _ in range(iters):
            r = algo.train()
            returns.append(r.get("episode_return_mean") or 0.0)
        dt = time.perf_counter() - t0
        env_steps = iters * train_batch
        updates = iters * epochs * (train_batch // minibatch)
        return {
            f"ppo_{name}_env_steps_per_s": round(env_steps / dt, 1),
            f"ppo_{name}_learner_updates_per_s": round(updates / dt, 1),
            f"ppo_{name}_iters": iters,
            f"ppo_{name}_final_return": round(float(returns[-1]), 1),
        }
    finally:
        algo.cleanup()


def bench_data(total_rows):
    """Two-stage batch-inference pipeline: transform -> 'model' matmul, pulled
    through the streaming executor with actor-pool concurrency."""
    import ray_tpu.data as rtd

    w = np.random.default_rng(0).standard_normal((64, 8)).astype(np.float32)

    def featurize(batch):
        x = np.asarray(batch["id"], np.float32)
        feats = np.stack([x * s for s in np.linspace(0.1, 6.4, 64)], axis=1)
        return {"feats": feats}

    def infer(batch):
        return {"pred": np.asarray(batch["feats"], np.float32) @ w}

    # warmup a small pipeline (worker spin-up + import cost out of the timing)
    (rtd.range(1024, parallelism=4).map_batches(featurize, concurrency=2)
        .map_batches(infer, concurrency=2).materialize())

    t0 = time.perf_counter()
    ds = (rtd.range(total_rows, parallelism=16)
          .map_batches(featurize, concurrency=2)
          .map_batches(infer, concurrency=2))
    n = 0
    for batch in ds.iter_batches():
        n += len(batch["pred"])
    dt = time.perf_counter() - t0
    assert n == total_rows, (n, total_rows)
    return {
        "data_pipeline_rows": total_rows,
        "data_pipeline_rows_per_s": round(total_rows / dt, 1),
        "data_pipeline_stages": "range -> featurize(64f) -> matmul(64x8), "
                                "actor concurrency 2+2, streaming executor",
    }


def bench_shuffle(total_rows, parallelism=16):
    """Sort throughput, PULL vs PUSH shuffle (the push
    scheduler existed for perf but was only correctness-tested). Reference:
    push_based_shuffle_task_scheduler.py — push bounds reduce fan-in with
    rounds of `merge_factor` eagerly folded into running merges, trading more
    (smaller) merge tasks for never holding every map output at once."""
    import ray_tpu.data as rtd
    from ray_tpu.data.context import DataContext

    rng = np.random.default_rng(0)
    vals = rng.integers(0, 1 << 30, total_rows)

    def run(push, merge_factor=8):
        ctx = DataContext.get_current()
        prev = (ctx.use_push_based_shuffle, ctx.push_shuffle_merge_factor)
        ctx.use_push_based_shuffle = push
        ctx.push_shuffle_merge_factor = merge_factor
        try:
            t0 = time.perf_counter()
            ds = (rtd.range(total_rows, parallelism=parallelism)
                  .map_batches(lambda b: {"key": vals[np.asarray(b["id"])]})
                  .sort("key"))
            n, last = 0, -1
            for batch in ds.iter_batches():
                k = np.asarray(batch["key"])
                assert k.size == 0 or (last <= k[0] and (np.diff(k) >= 0).all())
                if k.size:
                    last = int(k[-1])
                n += k.size
            dt = time.perf_counter() - t0
            assert n == total_rows, (n, total_rows)
            return round(total_rows / dt, 1)
        finally:
            ctx.use_push_based_shuffle, ctx.push_shuffle_merge_factor = prev

    run(False)  # warmup: worker spin-up out of the timing
    pull = run(False)
    push_by_factor = {f: run(True, f) for f in (4, 8, 16)}
    best_factor = max(push_by_factor, key=push_by_factor.get)
    return {
        "shuffle_sort_rows": total_rows,
        "shuffle_sort_pull_rows_per_s": pull,
        "shuffle_sort_push_rows_per_s": push_by_factor[best_factor],
        "shuffle_push_merge_factor": best_factor,
        "shuffle_push_by_merge_factor": push_by_factor,
        "shuffle_note": (
            "single-host sandbox: push's bounded fan-in pays off at map-task "
            "counts >> merge_factor and under memory pressure (its reason to "
            "exist on pods); at small scale the extra merge rounds cost more"),
    }


def _tpu_learner_body(batch=4096, minibatch=1024, iters=20):
    """PPO learner update jitted on THIS process's default jax backend
    (RL gets a device-side number). Synthetic GAE-processed
    batch + toy MLP — measures the jitted loss->grad->adam path, not gym."""
    import time as _time

    import gymnasium as gym
    import jax
    import numpy as _np

    from ray_tpu.rllib.algorithms.ppo import PPOConfig, PPOLearner
    from ray_tpu.rllib.core.rl_module import Columns, RLModuleSpec

    obs_dim, n_act = 64, 6
    cfg = (PPOConfig().training(lr=3e-4, train_batch_size=batch,
                                minibatch_size=minibatch, num_epochs=1)
           .debugging(seed=0))
    learner = PPOLearner(cfg, RLModuleSpec(
        observation_space=gym.spaces.Box(-1.0, 1.0, (obs_dim,), _np.float32),
        action_space=gym.spaces.Discrete(n_act),
        model_config={"fcnet_hiddens": [256, 256]}))
    learner.build()
    rng = _np.random.default_rng(0)
    b = {
        Columns.OBS: rng.standard_normal((batch, obs_dim)).astype(_np.float32),
        Columns.ACTIONS: rng.integers(0, n_act, batch).astype(_np.int32),
        Columns.ACTION_LOGP: _np.full((batch,), -_np.log(n_act), _np.float32),
        Columns.ADVANTAGES: rng.standard_normal(batch).astype(_np.float32),
        Columns.VALUE_TARGETS: rng.standard_normal(batch).astype(_np.float32),
    }
    learner.update(b)  # warmup: jit compile excluded from timing
    t0 = _time.perf_counter()
    for _ in range(iters):
        learner.update(b)
    dt = _time.perf_counter() - t0
    updates = iters * (batch // minibatch)
    return {
        "tpu_learner_backend": jax.default_backend(),
        "tpu_learner_batch": batch,
        "tpu_learner_minibatch": minibatch,
        "tpu_learner_updates_per_s": round(updates / dt, 1),
        "tpu_learner_update_ms": round(dt / updates * 1e3, 3),
    }


def bench_tpu_learner():
    """Run _tpu_learner_body in a subprocess WITHOUT JAX_PLATFORMS=cpu so the
    chip is that child's alone: this driver and its workers are pinned to the
    CPU backend and never open it."""
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import json, bench_rllib; "
         "print('RESULT ' + json.dumps(bench_rllib._tpu_learner_body()))"],
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        return {"tpu_learner_error": proc.stderr.strip()[-400:]}
    line = next(ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def main():
    import ray_tpu

    ray_tpu.init(num_cpus=4, worker_env={"JAX_PLATFORMS": "cpu"})
    results = {
        "note": ("CPU sandbox, 4-CPU worker pool: PPO rates are bounded by "
                 "Python gym stepping + host GAE, Data rates by pickled block "
                 "transport between actor-pool workers — not by the device "
                 "paths these pipelines feed on TPU hardware.")
    }
    try:
        results.update(bench_ppo(
            "CartPole-v1", "cartpole",
            train_batch=1024, minibatch=256, epochs=4, iters=2 if QUICK else 8))
        results.update(bench_ppo(
            SyntheticAtariEnv, "atari_synth",
            train_batch=512, minibatch=128, epochs=2, iters=1 if QUICK else 4))
        results.update(bench_data(4096 if QUICK else 100_000))
        results.update(bench_shuffle(8192 if QUICK else 200_000))
        results.update(bench_tpu_learner())
    finally:
        ray_tpu.shutdown()
    for k, v in results.items():
        print(f"{k}: {v}")
    with open(os.path.join(os.path.dirname(__file__) or ".", "RL_BENCH.json"), "w") as f:
        json.dump(results, f, indent=2)
    print("wrote RL_BENCH.json")


if __name__ == "__main__":
    from ray_tpu.core.accelerators import ensure_compile_cache_dir

    ensure_compile_cache_dir()  # inherited by the child that takes the chip
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
