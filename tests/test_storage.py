"""Pluggable checkpoint storage (reference python/ray/train/_internal/storage.py:358).

The mock:// scheme is a directory-backed remote store reachable only through
explicit upload/download — code passing these tests never relied on workers
and controller sharing a filesystem.
"""
import json
import os
import uuid

import pytest

from ray_tpu.air.config import CheckpointConfig
from ray_tpu.train import Checkpoint
from ray_tpu.train import storage
from ray_tpu.train.checkpoint_manager import CheckpointManager


@pytest.fixture()
def mock_root(tmp_path, monkeypatch):
    root = str(tmp_path / "bucket")
    monkeypatch.setenv("RAY_TPU_MOCK_FS_ROOT", root)
    return root


def _make_local_ckpt(tmp_path, step):
    d = tmp_path / f"src_{step}"
    d.mkdir()
    (d / "state.json").write_text(json.dumps({"step": step}))
    (d / "nested").mkdir()
    (d / "nested" / "w.bin").write_bytes(b"\x01" * 100)
    return str(d)


def test_upload_download_roundtrip(mock_root, tmp_path):
    src = _make_local_ckpt(tmp_path, 1)
    storage.upload_dir(src, "mock://exp/ck")
    assert storage.exists("mock://exp/ck")
    assert sorted(storage.listdir("mock://exp")) == ["ck"]
    dst = str(tmp_path / "down")
    storage.download_dir("mock://exp/ck", dst)
    assert json.load(open(os.path.join(dst, "state.json")))["step"] == 1
    assert open(os.path.join(dst, "nested", "w.bin"), "rb").read() == b"\x01" * 100
    storage.delete("mock://exp/ck")
    assert not storage.exists("mock://exp/ck")


def test_persist_dir_all_directions(mock_root, tmp_path):
    # local -> remote consumes the local copy (worker-side upload)
    src = _make_local_ckpt(tmp_path, 2)
    storage.persist_dir(src, "mock://p/a")
    assert not os.path.exists(src) and storage.exists("mock://p/a")
    # remote -> remote is a rename (controller moving staging -> durable)
    storage.persist_dir("mock://p/a", "mock://p/b")
    assert storage.exists("mock://p/b") and not storage.exists("mock://p/a")
    # remote -> local downloads
    dst = str(tmp_path / "out")
    storage.persist_dir("mock://p/b", dst)
    assert json.load(open(os.path.join(dst, "state.json")))["step"] == 2


def test_remote_checkpoint_metadata_and_as_directory(mock_root, tmp_path):
    src = _make_local_ckpt(tmp_path, 3)
    storage.upload_dir(src, "mock://ck3")
    ckpt = Checkpoint("mock://ck3")
    assert ckpt.is_remote
    ckpt.update_metadata({"index": 7})
    assert ckpt.get_metadata() == {"index": 7}
    with ckpt.as_directory() as d:
        assert d != "mock://ck3" and os.path.isdir(d)
        assert json.load(open(os.path.join(d, "state.json")))["step"] == 3
        local = d
    assert not os.path.exists(local)  # temp download cleaned up


def test_checkpoint_manager_remote_retention_and_resume_scan(mock_root, tmp_path):
    uri = "mock://runs/exp1"
    mgr = CheckpointManager(uri, CheckpointConfig(num_to_keep=2))
    for step in range(3):
        src = _make_local_ckpt(tmp_path, step)
        mgr.register(Checkpoint(src), {"step": step})
    names = storage.listdir(uri)
    assert "checkpoint_000001" in names and "checkpoint_000002" in names
    assert "checkpoint_000000" not in names  # retention pruned via the fs
    assert mgr.latest_checkpoint.path == storage.join(uri, "checkpoint_000002")
    # a fresh manager (head restart / rerun) rebuilds its index from the URI
    mgr2 = CheckpointManager(uri, CheckpointConfig(num_to_keep=2))
    assert mgr2.latest_checkpoint.path.endswith("checkpoint_000002")
    with mgr2.latest_checkpoint.as_directory() as d:
        assert json.load(open(os.path.join(d, "state.json")))["step"] == 2


def test_retention_keeps_what_the_running_attempt_resumed_from(mock_root, tmp_path):
    """Reports are no barrier: rank 0 can report (and the controller register)
    num_to_keep checkpoints while another rank still downloads the one both
    were started from. Retention must not take it from under that rank."""
    uri = "mock://runs/exp2"
    mgr = CheckpointManager(uri, CheckpointConfig(num_to_keep=2))
    for step in range(2):
        mgr.register(Checkpoint(_make_local_ckpt(tmp_path, step)), {"step": step})
    rerun = CheckpointManager(uri, CheckpointConfig(num_to_keep=2))
    rerun.resume_point = rerun.latest_checkpoint
    for step in range(2, 4):
        rerun.register(Checkpoint(_make_local_ckpt(tmp_path, step)), {"step": step})
    assert sorted(n for n in storage.listdir(uri) if n.startswith("checkpoint_")) == [
        "checkpoint_000001", "checkpoint_000002", "checkpoint_000003"]
    with rerun.resume_point.as_directory() as d:
        assert json.load(open(os.path.join(d, "state.json")))["step"] == 1
    # the next attempt starts from the newest, and the old pin goes
    rerun.resume_point = rerun.latest_checkpoint
    rerun.register(Checkpoint(_make_local_ckpt(tmp_path, 4)), {"step": 4})
    assert sorted(n for n in storage.listdir(uri) if n.startswith("checkpoint_")) == [
        "checkpoint_000003", "checkpoint_000004"]


def test_trainer_with_remote_storage_and_resume(rt, tmp_path):
    """End-to-end: workers UPLOAD checkpoints to mock:// storage on report;
    the result carries URIs; a rerun under the same name resumes from the URI
    (downloaded on whatever host runs the worker)."""
    from ray_tpu.air import CheckpointConfig as CC
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train import JaxConfig, JaxTrainer
    import ray_tpu.train as train

    name = f"exp_{uuid.uuid4().hex[:8]}"

    def loop(config):
        import json as _json
        import os as _os
        import tempfile

        ctx = train.get_context()
        ckpt = train.get_checkpoint()
        start = 0
        if ckpt is not None:
            assert ckpt.is_remote  # resume streams DOWN from storage
            with ckpt.as_directory() as d:
                start = _json.load(open(_os.path.join(d, "s.json")))["step"] + 1
        for step in range(start, start + 2):
            checkpoint = None
            if ctx.get_world_rank() == 0:
                d = tempfile.mkdtemp()
                _json.dump({"step": step}, open(_os.path.join(d, "s.json"), "w"))
                checkpoint = train.Checkpoint.from_directory(d)
            train.report({"step": step}, checkpoint=checkpoint)

    def make_trainer():
        return JaxTrainer(
            loop,
            backend_config=JaxConfig(collective_group=False),
            scaling_config=ScalingConfig(num_workers=2, cpus_per_worker=1.0),
            run_config=RunConfig(name=name, storage_path="mock://results",
                                 checkpoint_config=CC(num_to_keep=2)),
        )

    result = make_trainer().fit()
    assert result.error is None, result.error
    assert result.metrics["step"] == 1
    assert result.checkpoint is not None and result.checkpoint.path.startswith("mock://")
    # second run resumes from the URI checkpoint
    result2 = make_trainer().fit()
    assert result2.error is None, result2.error
    assert result2.metrics["step"] == 3


def test_file_uri_is_local(tmp_path):
    """file:// URIs strip to plain local paths (no garbage ./file: dirs)."""
    target = tmp_path / "nfs" / "exp"
    target.mkdir(parents=True)
    ckpt = Checkpoint(f"file://{target}")
    assert not ckpt.is_remote
    assert ckpt.path == str(target)
    mgr = CheckpointManager(f"file://{tmp_path}/nfs/exp2")
    assert mgr.storage_dir == str(tmp_path / "nfs" / "exp2")
    assert os.path.isdir(mgr.storage_dir)


def test_empty_dirs_roundtrip(mock_root, tmp_path):
    src = tmp_path / "src"
    (src / "empty").mkdir(parents=True)
    (src / "f.txt").write_text("x")
    storage.upload_dir(str(src), "mock://ed")
    dst = str(tmp_path / "dst")
    storage.download_dir("mock://ed", dst)
    assert os.path.isdir(os.path.join(dst, "empty"))
    assert open(os.path.join(dst, "f.txt")).read() == "x"
