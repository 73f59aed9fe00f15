"""The Sarvam-MLA serving cell's CPU tests, under tier-1: every case of
``benchmark/tests/test_sarvam_mla.py`` (the tiny cell sound and with each
fault planted, the float8 control, the new readers' arithmetic, the counts
and the configuration by hand against the catalog row), collected here by
import so that a change to that file changes tier-1 with no second edit."""
from benchmark.tests.test_sarvam_mla import *  # noqa: F401,F403
