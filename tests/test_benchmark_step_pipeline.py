"""The live benchmark's step-pipeline tests, under tier-1: every case of
``benchmark/tests/test_step_pipeline.py`` (the per-step table of
``benchmark/lib/step_pipeline.py`` and the six per-layer readers on it),
collected here by import so that a change to that file changes tier-1 with no
second edit."""
from benchmark.tests.test_step_pipeline import *  # noqa: F401,F403
