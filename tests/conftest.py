import numpy as np
import pytest


def pytest_configure(config):
    # Exhaustive sweeps (large-shape grad walls) ride behind -m slow so
    # tools/verify.sh --fast and local iteration can deselect them with
    # -m "not slow"; the tier-1 run executes everything.
    config.addinivalue_line(
        "markers", "slow: exhaustive sweep; deselect with -m 'not slow'")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _bound_live_executables():
    # The full suite JITs thousands of programs into one process; past
    # ~500 tests the accumulated live executables can segfault XLA's CPU
    # client inside a later (tiny, unrelated) backend_compile. Dropping
    # the compilation caches at module teardown bounds that population;
    # each module recompiles its own programs, which it would on a
    # standalone run anyway.
    yield
    import jax

    jax.clear_caches()
