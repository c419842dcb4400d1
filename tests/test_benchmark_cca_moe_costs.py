"""benchmark/tests/test_cca_moe_costs.py, collected by tier-1 (``pytest tests/``) case
by case; one re-export module a file so that ``--dist loadfile`` spreads
them. README "Tests" says why."""

from benchmark.tests.test_cca_moe_costs import *  # noqa: F401,F403
