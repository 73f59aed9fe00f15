"""The live benchmark's own CPU tests, under tier-1: every case of
``benchmark/tests/test_benchmark.py`` (the FLOP counts by hand, the trace
reducer, the seeded traffic, and the controls and planted faults that keep
``correct`` able to fail), collected here by import so that a change to that
file changes tier-1 with no second edit."""
from benchmark.tests.test_benchmark import *  # noqa: F401,F403
