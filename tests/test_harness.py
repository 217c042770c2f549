"""The per-test time limit of tests/conftest.py, tried on a suite that hangs.

Each case runs pytest in a subprocess on a temporary test file, under this
repo's conftest with its two constants made small, the way the driver runs
tier-1 (xdist, `--dist loadfile`).
"""
import os
import subprocess
import sys
import textwrap
import xml.etree.ElementTree as ET

import pytest

from ray_tpu.test_utils import wait_for_condition

HERE = os.path.dirname(os.path.abspath(__file__))

CONFTEST = f"""
import importlib.util
spec = importlib.util.spec_from_file_location("repo_conftest", {os.path.join(HERE, "conftest.py")!r})
repo_conftest = importlib.util.module_from_spec(spec)
spec.loader.exec_module(repo_conftest)
repo_conftest.TEST_TIMEOUT_S = 2
repo_conftest.BACKSTOP_MARGIN_S = 3
for _name in dir(repo_conftest):
    if _name.startswith("pytest_"):
        globals()[_name] = getattr(repo_conftest, _name)
"""

# `first` never returns and owns a child process; `second` is queued behind it
# in the same file.
SUITE = """
import os, signal, subprocess, time
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

@pytest.fixture
def child():
    p = subprocess.Popen(["sleep", "300"])
    with open(os.path.join(HERE, "child.pid"), "w") as f:
        f.write(str(p.pid))
    yield p
    with open(os.path.join(HERE, "finalized"), "w"):
        pass
    p.kill()
    p.wait()

def test_first(child):
    {hang}

def test_second():
    pass
"""

HANGS = {
    # a wait in Python: the alarm raises in the test and its finalizers run
    "python-wait": "time.sleep(300)",
    # what a wait inside a C call looks like to the alarm: its handler never
    # runs, and only the backstop ends the test, by killing the worker
    "deaf-to-signals": ("signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})\n"
                        "    time.sleep(300)"),
}


def _alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except FileNotFoundError:
        return False


@pytest.mark.parametrize("hang", sorted(HANGS))
def test_a_test_that_never_returns_costs_one_test(tmp_path, hang):
    (tmp_path / "conftest.py").write_text(CONFTEST)
    (tmp_path / "test_hangs.py").write_text(
        textwrap.dedent(SUITE).replace("{hang}", HANGS[hang]))
    junit = tmp_path / "junit.xml"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", "-p", "xdist",
         "-n", "1", "--dist", "loadfile", f"--junitxml={junit}", "--rootdir", str(tmp_path),
         str(tmp_path / "test_hangs.py")],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 1, out

    # failed by name, in the log and in the junit file; the next test of the
    # file ran and passed
    assert "FAILED test_hangs.py::test_first" in out, out
    cases = list(ET.parse(junit).getroot().iter("testcase"))
    bad = {c.get("name") for c in cases
           if c.find("failure") is not None or c.find("error") is not None}
    assert bad == {"test_first"}, out
    assert "test_second" in {c.get("name") for c in cases}, out
    # every thread's stack was printed: the line the test waited on is in it
    assert "test_hangs.py" in out and "in test_first" in out, out

    if hang == "python-wait":
        assert "ran past the per-test limit of 2 s" in out, out
        assert (tmp_path / "finalized").exists(), "the cut test's fixture was not finalized"
        assert "node down" not in out, out
    else:
        # the worker was killed, xdist said so and handed the rest of the file
        # to a new worker, which did not run the killer again
        assert "node down" in out, out
        assert "killed the worker that ran it before" in out, out
    pid = int((tmp_path / "child.pid").read_text())
    wait_for_condition(lambda: not _alive(pid), timeout=10,
                       message=f"the cut test's child process {pid} outlived the run")
