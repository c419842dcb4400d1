"""benchmark/tests/test_reader_moe_skip_share.py, collected by tier-1 (``pytest tests/``) case
by case; one re-export module a file so that ``--dist loadfile`` spreads
them. README "Tests" says why."""

from benchmark.tests.test_reader_moe_skip_share import *  # noqa: F401,F403
