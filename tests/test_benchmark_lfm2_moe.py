"""The LFM2-MoE serving cell's CPU tests, under tier-1: every case of
``benchmark/tests/test_lfm2_moe.py`` (the tiny cell sound and with each
fault planted, the low-precision control, the new readers on a hand-made
trace, the counts and the configuration by hand against the catalog row),
collected here by import so that a change to that file changes tier-1 with
no second edit."""
from benchmark.tests.test_lfm2_moe import *  # noqa: F401,F403
