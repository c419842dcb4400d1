"""benchmark/tests/test_reader_cca_attn_ms_per_step.py, collected by tier-1 (``pytest tests/``) case
by case; one re-export module a file so that ``--dist loadfile`` spreads
them. README "Tests" says why."""

from benchmark.tests.test_reader_cca_attn_ms_per_step import *  # noqa: F401,F403
