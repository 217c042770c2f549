"""ray_tpu.tune tests (reference strategy: python/ray/tune/tests/)."""
import pytest

from ray_tpu import tune
from ray_tpu.tune.tune_controller import ERROR, TERMINATED


@pytest.fixture(autouse=True)
def _cluster(rt):
    yield


def test_grid_search_function_trainable(rt):
    def objective(config):
        for i in range(3):
            tune.report({"loss": (config["x"] - 2) ** 2 + i * 0.0})

    tuner = tune.Tuner(
        objective,
        param_space={"x": tune.grid_search([0, 1, 2, 3])},
        tune_config=tune.TuneConfig(metric="loss", mode="min"),
    )
    grid = tuner.fit()
    assert len(grid) == 4
    best = grid.get_best_result("loss", "min")
    assert best.config["x"] == 2
    assert best.metrics["loss"] == 0


def test_class_trainable_and_stop_criteria(rt):
    class Quad(tune.Trainable):
        def setup(self, config):
            self.x = config["x"]

        def step(self):
            return {"score": self.x * self._iteration}

    grid = tune.run(Quad, config={"x": tune.grid_search([1, 5])}, stop={"training_iteration": 4})
    assert len(grid) == 2
    for r in grid:
        assert r.metrics["training_iteration"] == 4


def test_random_search_spaces(rt):
    seen = []

    def obj(config):
        seen.append(config)
        tune.report({"v": config["lr"], "done": True})

    grid = tune.Tuner(
        obj,
        param_space={"lr": tune.loguniform(1e-5, 1e-1), "b": tune.choice([8, 16])},
        tune_config=tune.TuneConfig(num_samples=5, seed=0),
    ).fit()
    assert len(grid) == 5
    for r in grid:
        assert 1e-5 <= r.config["lr"] <= 1e-1
        assert r.config["b"] in (8, 16)


def test_asha_stops_bad_trials(rt):
    def objective(config):
        for i in range(20):
            tune.report({"loss": config["x"] + i * 0.001})

    sched = tune.AsyncHyperBandScheduler(metric="loss", mode="min", grace_period=2, max_t=20)
    # sequential trials -> deterministic rung comparisons
    grid = tune.run(
        objective,
        config={"x": tune.grid_search([0.0, 1.0, 2.0, 3.0])},
        scheduler=sched,
        max_concurrent_trials=1,
    )
    iters = {r.config["x"]: r.metrics["training_iteration"] for r in grid}
    assert iters[0.0] == 20  # best trial runs to max_t
    assert iters[1.0] == iters[2.0] == iters[3.0] == 2  # cut at the first rung


def test_checkpoint_restore_on_failure(rt):
    class Flaky(tune.Trainable):
        def setup(self, config):
            self.acc = 0

        def step(self):
            self.acc += 1
            if self.acc == 3 and not getattr(self, "acc_restored", False):
                raise RuntimeError("boom")  # fails until restarted from a checkpoint
            return {"acc": self.acc}

        def save_checkpoint(self):
            return {"acc": self.acc}

        def load_checkpoint(self, state):
            self.acc = state["acc"]
            self.acc_restored = True

    import ray_tpu
    from ray_tpu.air import CheckpointConfig, FailureConfig, RunConfig

    grid = tune.Tuner(
        Flaky,
        param_space={},
        run_config=RunConfig(
            stop={"training_iteration": 6},
            failure_config=FailureConfig(max_failures=2),
            checkpoint_config=CheckpointConfig(checkpoint_frequency=1),
        ),
    ).fit()
    r = grid[0]
    assert r.error is None
    assert r.metrics["training_iteration"] == 6


def test_pbt_exploits(rt, tmp_path):
    started = str(tmp_path)

    def objective(config):
        import os
        import time

        from ray_tpu.test_utils import wait_for_condition

        # PBT exploits within a population. On a loaded machine one trial's
        # actor can come up seconds before the other's, and 20 unpaced
        # iterations take milliseconds: the trials start their iterations
        # together and take a second over them, or the weak one has finished
        # before there is anyone to copy from.
        open(os.path.join(started, f"lr{config['lr']}"), "w").close()
        wait_for_condition(lambda: len(os.listdir(started)) >= 2, timeout=120,
                           interval=0.005,
                           message="the other trial of the population never started")
        score = 0.0
        ck = tune.get_checkpoint()
        if ck is not None:
            score = ck["score"]
        lr = config["lr"]
        for i in range(20):
            time.sleep(0.05)
            score += lr  # higher lr -> faster score growth
            tune.report({"score": score}, checkpoint={"score": score})

    sched = tune.PopulationBasedTraining(
        metric="score", mode="max", perturbation_interval=5,
        hyperparam_mutations={"lr": tune.uniform(0.1, 1.0)}, seed=0,
    )
    # PBT restarts exploited function trials, so a stop criterion bounds the run
    grid = tune.run(
        objective,
        config={"lr": tune.grid_search([0.1, 0.9])},
        scheduler=sched,
        max_concurrent_trials=2,
        stop={"training_iteration": 30},
    )
    assert len(grid) == 2
    # exploit copies the strong trial's state; both end with competitive scores
    scores = sorted(r.metrics["score"] for r in grid)
    assert scores[0] > 0.1 * 30 + 0.5  # weak trial was boosted past its pure-0.1-lr ceiling
