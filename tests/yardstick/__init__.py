"""Tests of the yardstick (BENCHMARK.json names this directory)."""

import sys

_PIN_THEN_RUN = (
    "import os, sys; "
    "os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[-2:]); "
    "os.execv(sys.executable, [sys.executable] + sys.argv[1:])"
)


def on_two_cores(*argv):
    """The command ``python <argv>`` held, with all it starts, to two
    cores: a rehearsal is a dozen processes, and the timed drills that
    run beside it under xdist miss their windows when it takes every
    core. (Not ``preexec_fn``: that forks a process that has threads.)
    """
    return [sys.executable, "-c", _PIN_THEN_RUN, *argv]
