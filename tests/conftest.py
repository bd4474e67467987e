"""Test fixtures. Must run before jax initializes: force CPU platform with 8
virtual devices so multi-chip sharding is tested without TPU hardware (the
reference had no distributed tests at all — see SURVEY.md §4)."""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# The suite is CPU-only whatever the machine holds: JAX_PLATFORMS=cpu in the
# environment does the same, this makes a bare `pytest` safe on a chip host.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(scope="session", autouse=True)
def _compile_cache_in_tmp(tmp_path_factory):
    """Unless the environment already places it, the persistent compile
    cache of a test session lives in the session's tmp dir: never the
    checkout's `.cache/jax` (the program's default when the variable is
    unset), never a previous run's entries — a "fresh compile is a miss"
    assertion must not depend on what ran yesterday. Child processes
    inherit the variable, so they share the session's cache. The cache
    earns its keep here: tests compile the same rounds again and again,
    and a cold session cache took the serial suite from ~815 s to ~650 s
    (PR 21, 8 cores). The driver runs the suite under `-p xdist -n 6
    --dist loadfile` with a limit of 1,470 s (`/root/TESTS_LAST_RUN.json`,
    ROADMAP.md D13); `tmp_path_factory` is a worker's own there, so each of
    the six workers keeps its own cache. One cache for the whole session
    was weighed and left (PR 45): no two workers compile the same program
    -- 0 keys in common among the 4,323 entries the six caches of a whole
    run held, a file's tests running in one worker -- and jax writes an
    entry in place, not by rename, so a second writer would only add the
    risk of a torn read. On the CPU a cache HIT makes XLA's loader print
    kilobytes of machine-feature warnings per executable: a test must never
    leave a child's output in a pipe it does not read."""
    from sparknet_tpu.utils.compile_cache import CACHE_DIR_ENV
    if CACHE_DIR_ENV in os.environ:
        yield
        return
    os.environ[CACHE_DIR_ENV] = str(tmp_path_factory.mktemp("jax-cache"))
    yield
    del os.environ[CACHE_DIR_ENV]


@pytest.fixture(autouse=True)
def _logs_to_tmp(tmp_path, monkeypatch):
    """Any code path that falls back to the default log location
    (RunConfig.workdir=None -> $SPARKNET_TPU_HOME) writes under tmp, never
    the repo root."""
    monkeypatch.setenv("SPARKNET_TPU_HOME", str(tmp_path))


@pytest.fixture(autouse=True)
def _precision_policy_isolated():
    """Restore the (thread-local) precision policy after every test: the
    bench arms set bfloat16 on the main thread and a leaked policy turns
    later f32-exactness tests red — a latent cross-file coupling that only
    shows when the whole suite runs in one process past test_bench."""
    import jax.numpy as jnp

    from sparknet_tpu import precision
    prev = ("bfloat16" if precision.compute_dtype() == jnp.bfloat16
            else "float32")
    yield
    precision.set_policy(prev)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session", params=["shard_map", "named"])
def trainer_cls(request):
    """Both layer-IR trainer implementations (r7): the shard_map replica-
    layout ParallelTrainer and the NamedSharding logical-state
    ShardedTrainer. Trainer-facing tests take this fixture so the parity
    pin is the test MATRIX itself — every round-pipeline, elastic, and
    health-layout behavior must hold under either implementation."""
    from sparknet_tpu.parallel import ParallelTrainer, ShardedTrainer
    return (ParallelTrainer if request.param == "shard_map"
            else ShardedTrainer)
