"""Test environment: force JAX onto 8 virtual CPU devices.

Per SURVEY.md §4 item 4: distributed paths (shard_map grad allreduce,
per-device RNG) are exercised on virtual CPU devices so the suite runs
anywhere, and Pallas kernels run in interpret mode. The chip is reached only
through ``chip_smoke.py`` (and later the benchmark), never from a test; what
the chip's COMPILER accepts is covered here by tests/test_chip_compile.py,
which compiles for a described v5e without one attached.

``JAX_PLATFORMS`` and ``XLA_FLAGS`` are read when jax is imported and when
the CPU client starts, so they are set here, before the first ``import
jax`` of the session (pytest imports conftest.py before any test module).
Keep this module free of any call that touches devices.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # also inherited by spawned children

import jax  # noqa: E402  (after the env block above)

# a test compiles what it tests: the persistent cache the CLIs place
# (utils/compile_cache.py) stays off for the session, so a stale entry never
# stands in for a compile and test runs write nothing into the checkout
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    # tier-1 filters with `-m "not slow"`; register the marker so strict
    # marker modes and --markers stay accurate (graftlint GL008 enforces it
    # on TPU-only test imports)
    config.addinivalue_line(
        "markers", "slow: needs real TPU hardware or long wall-clock; "
        "excluded from tier-1 (-m 'not slow')"
    )
    config.addinivalue_line(
        "markers", "no_sanitize: opted out of the --sanitize transfer "
        "guard (the test's PURPOSE is an implicit transfer or a NaN path)"
    )


def pytest_addoption(parser):
    parser.addoption(
        "--sanitize", action="store_true", default=False,
        help="run every test under jax.transfer_guard('disallow') + "
        "jax.debug_nans: the runtime cross-check of graftlint's "
        "GL001/GL013 zero-implicit-transfer claim (scripts/sanitize.sh "
        "drives this over the hot-path tier-1 subset)",
    )


import pytest  # noqa: E402  (after the backend-forcing block above)


@pytest.fixture(autouse=True)
def _sanitizer_gate(request):
    """With --sanitize, fail any test that performs an implicit host<->
    device transfer (explicit device_put/device_get stay allowed — the
    whole point is that every transfer must be a visible decision) or
    produces a NaN. graftlint proves the claim lexically; this proves it
    at runtime."""
    if not request.config.getoption("--sanitize") or \
            request.node.get_closest_marker("no_sanitize"):
        yield
        return
    with jax.transfer_guard("disallow"), jax.debug_nans(True):
        yield


@pytest.fixture
def small_blocks(monkeypatch):
    """Row blocks of 512 bytes in ``data/batcher.py``, so that a tiny cached
    dataset's feature gathers run on the gather pool (tests/test_data.py: a
    resnet row is 768 bytes, a c3d row 384, so blocks of one row and of two);
    a pool of two where the machine gets none."""
    from concurrent.futures import ThreadPoolExecutor

    from cst_captioning_tpu.data import batcher

    monkeypatch.setattr(batcher, "_BLOCK_BYTES", 512)
    if batcher._gather_pool()[0] is not None:
        yield
        return
    with ThreadPoolExecutor(2, thread_name_prefix="collate.gather") as pool:
        monkeypatch.setattr(batcher, "_pool", pool)
        monkeypatch.setattr(batcher, "_pool_width", 2)
        yield
