"""What a train driver does on its loop's thread between the last warm-up step and
`window_start`, so that every run measures the same class of process."""
import os

BURST_CALLS = 3000


def settle_host() -> None:
    """A burst of system calls on the calling thread, which has to be the one that runs the window.

    The chip machine's processes come in two classes, a step's host share ~3 ms or ~7, by a state
    of its sandboxed system-call path and not of the program: the device step is the same to
    0.01 ms, every host call of a slow process is slower, no other thread, collection, poll or
    switch interval is the cause (PERF.md, PR 35). 3,000 `os.stat("/")` on the loop's thread
    before the window made 19 of 19 processes of the fast class, 6 of 23 without (PR 35, the
    Nemotron cell); in the SDAR cell 3 of 10 runs were slow without it (448 ms a step beside
    443.5: PR 50) and none since. Without it, which class a side's median falls in is a draw: PRs
    47, 55 and 56 were refused by it on the Solar-Open2 cell (8,192 tokens in 223 or 227 ms a
    step, the same device step: PR 57). The calls fall inside `setup_s`: milliseconds of 28-55 s.
    Nothing of it is inside the window.
    """
    for _ in range(BURST_CALLS):
        os.stat("/")
