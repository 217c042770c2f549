"""IMPALA/APPO tests (reference strategy: rllib regression configs on CartPole)."""
import numpy as np
import pytest

from ray_tpu.rllib.algorithms.appo import APPOConfig
from ray_tpu.rllib.algorithms.impala import IMPALAConfig, pad_time_major


@pytest.fixture(autouse=True)
def _cluster(rt):
    yield


def _fake_episode(T, terminated=True, obs_dim=4):
    return {
        "obs": np.random.randn(T, obs_dim).astype(np.float32),
        "next_obs_last": np.random.randn(obs_dim).astype(np.float32),
        "actions": np.random.randint(0, 2, size=T),
        "rewards": np.ones(T, np.float32),
        "terminated": terminated,
        "truncated": False,
        "action_logp": np.full(T, -0.69, np.float32),
        "vf_preds": np.zeros(T, np.float32),
    }


def test_pad_time_major_shapes_and_split():
    eps = [_fake_episode(10), _fake_episode(70, terminated=False)]
    batch = pad_time_major(eps, max_T=32, b_bucket=4)
    # 70 splits into 32+32+6 -> 4 pieces total, bucketed to 4
    assert batch["obs_ext"].shape == (4, 33, 4)
    assert batch["mask"].sum() == 80
    assert batch["lens"].tolist() == [10, 32, 32, 6]
    # only the 10-step piece terminated; split interior pieces must bootstrap
    assert batch["terminated"].tolist() == [1.0, 0.0, 0.0, 0.0]
    # bootstrap obs sits at row lens[b]
    np.testing.assert_allclose(batch["obs_ext"][1, 32], eps[1]["obs"][32].reshape(-1))


def test_vtrace_matches_one_step_td():
    """With on-policy logp (rho=c=1) and T=1, the V-trace target is exactly
    r + gamma*V(next): vf_loss == 0.5*(r + gamma*V(next) - V(s))^2."""
    from ray_tpu.rllib.algorithms.impala import IMPALAConfig, IMPALALearner
    from ray_tpu.rllib.core.rl_module import Columns, RLModuleSpec
    import gymnasium as gym

    env = gym.make("CartPole-v1")
    spec = RLModuleSpec(module_class=None, observation_space=env.observation_space,
                        action_space=env.action_space, model_config={})
    cfg = IMPALAConfig().environment("CartPole-v1")
    learner = IMPALALearner(cfg, spec)
    learner.build()
    # (a seeded draw: of unseeded ones 1 in ~850 lands where r + gamma*V(next) - V(s) is under 3e-3 and its
    # square cannot be held to an rtol of 1e-4 in float32, whatever compiles the loss)
    np.random.seed(0)
    ep = _fake_episode(1, terminated=False)
    # make the behaviour logp exactly on-policy so rho = c = 1
    out = learner.module.apply_np(
        learner.params, ep["obs"].reshape(1, -1).astype(np.float32))
    dist = learner.module.action_dist_cls
    ep["action_logp"] = dist.logp_np(out["action_dist_inputs"], ep["actions"])
    batch = pad_time_major([ep], max_T=1, b_bucket=1)
    loss, aux = learner.compute_losses(learner.params, batch)
    v_s = float(out[Columns.VF_PREDS][0])
    out_next = learner.module.apply_np(
        learner.params, ep["next_obs_last"].reshape(1, -1).astype(np.float32))
    v_next = float(out_next[Columns.VF_PREDS][0])
    expected_vf_loss = 0.5 * (1.0 + cfg.gamma * v_next - v_s) ** 2
    np.testing.assert_allclose(float(aux["vf_loss"]), expected_vf_loss, rtol=1e-4)
    np.testing.assert_allclose(float(aux["mean_rho"]), 1.0, rtol=1e-5)
    env.close()


def test_impala_improves_cartpole(rt):
    config = (
        IMPALAConfig()
        .environment("CartPole-v1")
        .env_runners(num_env_runners=2, num_envs_per_env_runner=4, rollout_fragment_length=32)
        .training(lr=1e-3, train_batch_size=512, gamma=0.99, entropy_coeff=0.005,
                  max_seq_len=64, broadcast_interval=1, num_epochs=4)
        .debugging(seed=0)
    )
    algo = config.build_algo()
    try:
        # an asynchronous learner beside five other test workers learns at the pace the machine
        # leaves it (16.7 -> 28.9 in 25 iterations in the driver's run of PR 44, where alone it
        # is there in a dozen): train until the improvement is seen, 75 iterations at most
        returns = []
        while len(returns) < 75 and not (len(returns) > 3 and max(returns[3:]) > returns[0] + 15):
            result = algo.train()
            returns.append(result.get("episode_return_mean") or 0.0)
        assert max(returns[3:]) > returns[0] + 15, returns
    finally:
        algo.cleanup()


def test_impala_with_aggregator_actors(rt):
    config = (
        IMPALAConfig()
        .environment("CartPole-v1")
        .env_runners(num_env_runners=1, num_envs_per_env_runner=2, rollout_fragment_length=32)
        .training(train_batch_size=128, num_aggregator_actors_per_learner=1)
        .debugging(seed=0)
    )
    algo = config.build_algo()
    try:
        result = algo.train()
        assert "total_loss" in result
        assert len(algo._aggregators) == 1
    finally:
        algo.cleanup()


def test_appo_runs_and_checkpoint_roundtrip(rt):
    config = (
        APPOConfig()
        .environment("CartPole-v1")
        .env_runners(num_env_runners=1, num_envs_per_env_runner=2, rollout_fragment_length=32)
        .training(train_batch_size=128, clip_param=0.3, use_kl_loss=True)
        .debugging(seed=0)
    )
    algo = config.build_algo()
    try:
        algo.train()
        state = algo.save_checkpoint()
        w_before = algo.get_weights()
        algo.train()
        algo.load_checkpoint(state)
        np.testing.assert_allclose(w_before["pi"][0]["w"], algo.get_weights()["pi"][0]["w"])
    finally:
        algo.cleanup()
