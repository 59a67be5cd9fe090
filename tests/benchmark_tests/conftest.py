"""Shared by the benchmark's tests: the repo on the path, the
rehearsal's cells (benchmark/testdata/rehearse) and a whole run of one
of them with the look for a chip skipped."""
import argparse
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import HERE, Harness  # noqa: E402

REHEARSE = os.path.join(HERE, "testdata", "rehearse")


@pytest.fixture(autouse=True)
def interpreted_flash(monkeypatch):
    """On the CPU the flash kernel runs interpreted."""
    monkeypatch.setenv("MXTPU_FLASH", "1")


@pytest.fixture(scope="module")
def harness():
    return Harness(REHEARSE, os.path.join(REHEARSE, "BENCHMARK.json"))


@pytest.fixture
def measure():
    """measure(cell, seed=3, seconds=0.2, trace=0): the result line of
    one rehearsal run, as run.py would print it."""
    from benchmark import run

    def go(cell, seed=3, seconds=0.2, trace=0):
        return run.measure(argparse.Namespace(
            workload=cell, seed=seed, seconds=seconds, trace=trace,
            rehearse=REHEARSE), look_for_chip=False)
    return go
