"""benchmark/tests/test_reader_eval_loop_spans.py, collected by tier-1 (``pytest tests/``) case
by case; one re-export module a file so that ``--dist loadfile`` spreads
them. README "Tests" says why."""

from benchmark.tests.test_reader_eval_loop_spans import *  # noqa: F401,F403
