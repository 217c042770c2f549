"""The train drivers' settle before the window (`lib/settle.py`, PR 57): what the function does,
and where each driver calls it. Milliseconds: the drivers' source is read, no step is run.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import ast
import os
import sys
import threading

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmarks.lib import settle  # noqa: E402


def test_the_burst_is_3000_stats_on_the_calling_thread(monkeypatch):
    calls = []
    monkeypatch.setattr(settle.os, "stat", lambda path: calls.append((path, threading.get_ident())))
    done = []
    worker = threading.Thread(target=lambda: (settle.settle_host(), done.append(threading.get_ident())))
    worker.start()
    worker.join()
    assert settle.BURST_CALLS == 3000 and "3,000" in settle.settle_host.__doc__
    assert calls == [("/", done[0])] * 3000  # every call on the thread that asked, none handed to another


def _lines(node, starts):
    """The lines of the calls under `node` whose source starts with one of `starts`."""
    return sorted(n.lineno for n in ast.walk(node) if isinstance(n, ast.Call) and ast.unparse(n).startswith(starts))


@pytest.mark.parametrize("driver", ["train", "train_family", "train_diffusion"])
def test_a_train_driver_settles_once_last_before_the_window(driver):
    with open(os.path.join(BENCH, "drivers", driver + ".py")) as f:
        tree = ast.parse(f.read())
    loop = next(n for n in tree.body if isinstance(n, ast.FunctionDef) and n.name == "_loop")
    (at,) = _lines(loop, "settle.settle_host()")
    assert _lines(tree, "settle.settle_host(") == [at]

    # the statements that follow the call in its block: the count of compiles first, then the
    # window's stamp with no step between them, then the window's loop
    block = next(body for n in ast.walk(loop) for body in [getattr(n, "body", [])]
                 if any(isinstance(s, ast.Expr) and s.lineno == at for s in body))
    after = [ast.unparse(s) for s in block if s.lineno > at]
    assert after[0] == "compiles_before = len(compiles)"
    stamp = after.index("stamps['window_start'] = time.time()")
    assert not any("step(" in s for s in after[:stamp])
    assert any("one_step(state, n)" in s for s in after[stamp:])

    # every warm-up step, and the parity where the driver makes it by the step, stand before the call
    before = _lines(loop, ("one_step(state, -1)", "step_parity("))
    assert before and before[-1] < at

    # no inline copy of the burst is left in a driver
    assert not _lines(tree, "os.stat(")
    assert not [n for n in ast.walk(tree) if isinstance(n, ast.Constant) and n.value == settle.BURST_CALLS]
