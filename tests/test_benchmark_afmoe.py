"""The AFMoE decoder's CPU tests, under tier-1: every case of
``benchmark/tests/test_afmoe.py`` (the windowed grouped-query flash kernels,
the drop-free expert layer and its share of a deployment, the program against
``refs/afmoe.py``, the tiny cell sound and with each fault planted), collected
here by import so that a change to that file changes tier-1 with no second
edit."""
from benchmark.tests.test_afmoe import *  # noqa: F401,F403
