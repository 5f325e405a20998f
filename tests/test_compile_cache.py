"""Persistent compilation cache helper (trainer/compile_cache.py) and
the one rule that places it (common/cachedir.py)."""

import os
import subprocess
import sys

import pytest

import jax

from dlrover_tpu.common import cachedir
from dlrover_tpu.trainer import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def default_at(tmp_path, monkeypatch):
    """A job that placed nothing, with the default moved to tmp_path."""
    d = str(tmp_path / "default")
    monkeypatch.delenv(cachedir.ENV_JAX_CACHE_DIR, raising=False)
    monkeypatch.setattr(cachedir, "default_cache_dir", lambda: d)
    return d


def test_default_dir_is_created_private(default_at):
    assert cachedir.resolve_cache_dir() == default_at
    # executables-only dir: private to this uid
    assert (os.stat(default_at).st_mode & 0o777) == 0o700


def test_foreign_owned_default_refused(default_at, monkeypatch):
    """Cache entries are deserialized executables: a pre-created dir
    owned by another uid must be refused, not adopted."""
    os.makedirs(default_at)
    real_stat = os.stat

    class FakeStat:
        def __init__(self, st):
            self.st_uid = st.st_uid + 1  # someone else
            self.st_mode = st.st_mode

    monkeypatch.setattr(
        os, "stat",
        lambda p, *a, **k: FakeStat(real_stat(p, *a, **k))
        if p == default_at else real_stat(p, *a, **k),
    )
    assert cachedir.resolve_cache_dir() is None
    assert compile_cache.setup_compilation_cache() is None


def test_adopted_loose_default_tightened_to_0700(default_at):
    """makedirs(mode=0o700) only applies on creation: a pre-existing
    same-uid dir with group/world access must be re-tightened before
    executables are loaded from it (the documented 0700 contract)."""
    os.makedirs(default_at, mode=0o755)
    os.chmod(default_at, 0o755)  # defeat umask
    assert cachedir.resolve_cache_dir() == default_at
    assert (os.stat(default_at).st_mode & 0o777) == 0o700


def test_default_dir_is_fixed_inside_the_checkout():
    d = cachedir.default_cache_dir()
    assert d == os.path.join(REPO, ".jax_cache")
    assert d == cachedir.default_cache_dir()
    # nothing that changes from run to run may be part of the path:
    # it is part of the compile cache's key
    for moving in (str(os.getpid()), str(os.getuid()) + "_", "tmp"):
        assert moving not in os.path.basename(d)
    # ... and git ignores it
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


_RESOLVE = """
import jax, jax.numpy as jnp
from dlrover_tpu.trainer import compile_cache as c
d = c.setup_compilation_cache()
x = jnp.ones((8, 8))
with c.cache_events() as seen:
    jax.jit(lambda a: (a @ a).sum() * 3.0).lower(x).compile()
print("|".join([d, jax.config.jax_compilation_cache_dir,
                str(seen["requests"]), str(seen["hits"])]))
"""


def _resolve_in_child(env_value, tmp_path):
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
        DLROVER_TPU_COMPILE_CACHE_MIN_SECS="0",
    )
    env.pop(cachedir.ENV_JAX_CACHE_DIR, None)
    if env_value is not None:
        env[cachedir.ENV_JAX_CACHE_DIR] = env_value
    out = subprocess.run(
        [sys.executable, "-c", _RESOLVE], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120, check=True,
    ).stdout.strip().splitlines()[-1]
    return out.split("|")


def test_jax_var_places_the_cache_in_every_process(tmp_path):
    """Set, jax's variable is used as given: the config holds what jax
    itself read from the environment, and the second process to
    compile a program gets it from the first — by jax's own event."""
    want = str(tmp_path / "from outside")
    first = _resolve_in_child(want, tmp_path)
    second = _resolve_in_child(want, tmp_path)
    assert first == [want, want, "1", "0"]
    assert second == [want, want, "1", "1"]


def test_unset_means_the_fixed_default_in_every_process(tmp_path):
    want = cachedir.default_cache_dir()
    first = _resolve_in_child(None, tmp_path)
    second = _resolve_in_child(None, tmp_path)
    assert first[:2] == second[:2] == [want, want]
    assert second[2:] == ["1", "1"]


def test_jax_var_wins_over_the_default(tmp_path, monkeypatch):
    named = str(tmp_path / "named")
    monkeypatch.setenv(cachedir.ENV_JAX_CACHE_DIR, named)
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(
        cachedir, "default_cache_dir", lambda: str(tmp_path / "other")
    )
    assert compile_cache.setup_compilation_cache() == named
    assert not (tmp_path / "other").exists()
    # the config is jax's to set from its variable, not this code's
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("named", [True, False],
                         ids=["jax_var_set", "jax_var_unset"])
def test_agent_worker_env_names_one_cache_dir(
    tmp_path, monkeypatch, named
):
    from dlrover_tpu.agent.elastic.training import (
        ElasticLaunchConfig,
        ElasticTrainingAgent,
    )

    want = str(tmp_path / "given")
    if named:
        monkeypatch.setenv(cachedir.ENV_JAX_CACHE_DIR, want)
    else:
        monkeypatch.delenv(cachedir.ENV_JAX_CACHE_DIR, raising=False)
        monkeypatch.setattr(cachedir, "default_cache_dir", lambda: want)

    class Client:
        master_addr = "localhost:1"

    agent = ElasticTrainingAgent.__new__(ElasticTrainingAgent)
    agent._config = ElasticLaunchConfig(
        min_nodes=1, max_nodes=1, entrypoint="x.py",
        env={"FROM_LAUNCHER": "1"},
    )
    agent._client = Client()
    agent._restart_count = 1
    env = agent._worker_env(1, 1, 0, 1, "localhost:2")
    assert env[cachedir.ENV_JAX_CACHE_DIR] == want
    assert env["FROM_LAUNCHER"] == "1"
    others = [
        k for k in env
        if k.startswith(("JAX_", "DLROVER_")) and "CACHE_DIR" in k
        and k != cachedir.ENV_JAX_CACHE_DIR
    ]
    assert others == []


def test_cache_entries_counts(tmp_path):
    d = str(tmp_path)
    assert compile_cache.cache_entries(d) == 0
    (tmp_path / "jit_f-abc-cache").mkdir()
    (tmp_path / ".hidden").write_text("x")
    assert compile_cache.cache_entries(d) == 1
    assert compile_cache.cache_entries(d + "/missing") == 0
