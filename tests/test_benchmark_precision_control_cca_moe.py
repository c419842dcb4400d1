"""benchmark/tests/test_precision_control_cca_moe.py, collected by tier-1 (``pytest tests/``) case
by case; one re-export module a file so that ``--dist loadfile`` spreads
them. README "Tests" says why."""

from benchmark.tests.test_precision_control_cca_moe import *  # noqa: F401,F403
