"""Where the persistent compile cache goes (utils/compile_cache.py): the
environment places it, then an explicit directory, then the fixed
in-checkout default — never a temp name."""
import os

import jax
import pytest

from sparknet_tpu.utils import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh_latch(monkeypatch):
    """Drop the first-caller-wins latch around a test, and give the rest
    of the session its cache back afterwards."""
    cc.reset_for_tests()
    yield
    cc.reset_for_tests()
    monkeypatch.undo()  # the session's own $JAX_COMPILATION_CACHE_DIR
    cc.init_compile_cache()


def test_env_dir_wins_over_explicit_argument(tmp_path, monkeypatch,
                                             fresh_latch):
    placed, asked = str(tmp_path / "placed"), str(tmp_path / "asked")
    monkeypatch.setenv(cc.CACHE_DIR_ENV, placed)
    with pytest.warns(RuntimeWarning, match="is ignored"):
        assert cc.init_compile_cache(asked) == placed
    assert jax.config.jax_compilation_cache_dir == placed
    assert os.path.isdir(placed) and not os.path.exists(asked)


def test_explicit_dir_used_when_env_unset(tmp_path, monkeypatch,
                                          fresh_latch):
    # the tier-1 run may have the variable set: clear it first
    monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    asked = str(tmp_path / "asked")
    assert cc.init_compile_cache(asked) == asked
    assert jax.config.jax_compilation_cache_dir == asked
    # first caller wins: the cache is process-global
    assert cc.init_compile_cache(str(tmp_path / "later")) == asked


def test_unset_default_is_the_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    want = os.path.join(REPO, ".cache", "jax")
    assert cc.DEFAULT_CACHE_DIR == want
    # resolved from the package's own path: the same from any cwd, on
    # every call — no pid, clock or mkdtemp in it
    monkeypatch.chdir("/")
    assert cc.resolve_cache_dir() == want == cc.resolve_cache_dir()
