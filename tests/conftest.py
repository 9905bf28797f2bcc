import os

# smoke tests and benches must see ONE device (the dry-run sets its own
# device count in a separate process)
os.environ.setdefault("XLA_FLAGS", "")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

#: XLA flag forcing 8 host devices -- the distributed suite runs its
#: payloads in subprocesses with this env so multi-device behaviour is
#: deterministic on single-device hosts (laptops, CI runners) without
#: perturbing the single-device main process.
DIST_XLA_FLAGS = "--xla_force_host_platform_device_count=8"


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def x64():
    """Enable f64 for one test, restoring the previous setting after."""
    import jax
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


@pytest.fixture(scope="session")
def dist_env():
    """Environment for the multi-device subprocess tests: 8 forced host
    devices + src on PYTHONPATH, pinned to the CPU (the children are CPU
    rehearsals by design; on a TPU host the parent may hold the chip)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = DIST_XLA_FLAGS
    env["PYTHONPATH"] = os.path.join(repo, "src")
    return env
