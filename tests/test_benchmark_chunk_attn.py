"""The live benchmark's ``chunk_attn_roofline`` reader tests, under tier-1:
every case of ``benchmark/tests/test_chunk_attn_roofline.py``, collected here
by import so that a change to that file changes tier-1 with no second
edit."""
from benchmark.tests.test_chunk_attn_roofline import *  # noqa: F401,F403
