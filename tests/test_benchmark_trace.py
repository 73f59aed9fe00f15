"""The live benchmark's trace tests, under tier-1: every case of
``benchmark/tests/test_program_trace.py`` (the arithmetic of
``benchmark/lib/program_trace.py`` and the per-layer readers on it),
collected here by import so that a change to that file changes tier-1 with no
second edit."""
from benchmark.tests.test_program_trace import *  # noqa: F401,F403
