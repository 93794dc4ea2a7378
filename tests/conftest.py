"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests run on the real single
CPU device; only launch/dryrun.py forces 512 placeholder devices."""
import jax
import pytest

from repro.parallel.mesh import make_mesh


@pytest.fixture(scope="session")
def mesh11():
    """A (1,1) ('data','model') mesh on the single CPU device — exercises
    every mesh code path (shard_map, flash decode, sharding rules) without
    multiple devices."""
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture
def sanitized_guards():
    """Opt-in runtime sanitizer: the test body runs under
    ``repro.analysis.sanitized()`` (transfer guard + debug-NaNs + live
    recompile/host-sync counters) and receives the live report."""
    from repro.analysis import sanitized

    with sanitized() as report:
        yield report
