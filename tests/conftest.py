import gc
import os
import sys

import pytest

# Tests see the default 1-device CPU backend (the dry-run sets its own
# XLA_FLAGS in a separate process -- never here).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(autouse=True, scope="module")
def _drop_jax_executables():
    """Free compiled executables between test modules.

    Every XLA:CPU JIT executable pins ~3 anonymous mmap regions
    (rx/ro/rw) for its emitted code; a full-suite run compiles tens of
    thousands of them and the process walks into vm.max_map_count
    (65530 here), where the next compile segfaults inside
    backend_compile instead of raising. Clearing per *module* bounds
    the map count at one module's working set (~5k) while keeping
    cache reuse across a module's parametrized tests.
    """
    yield
    import jax

    jax.clear_caches()
    gc.collect()


@pytest.fixture
def zo_step():
    """One sgd ZO step from bare params, through ``build_strategy``:
    ``zo_step(loss_fn, params, batch, seed, cfg, mask=None,
    estimator="fused") -> (params, aux)``. The fused estimator donates
    ``params``."""
    from repro.core import build_strategy

    def step(loss_fn, params, batch, seed, cfg, mask=None,
             estimator="fused"):
        strat = build_strategy(estimator, "sgd")
        state, aux = strat.step(loss_fn, strat.init_state(params, cfg),
                                batch, seed, cfg, mask)
        return state.params, aux

    return step
