"""The one rule for where the persistent compile cache lives
(``paddle_tpu/utils/compile_cache.py``): the environment's directory
when ``JAX_COMPILATION_CACHE_DIR`` is set — and then nothing is set in
code — otherwise ``<checkout>/.jax_cache``; never a path built from a
temporary name, a process id or the time."""

import os
import re
import subprocess
import sys

import pytest

import jax

from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean(monkeypatch):
    """configure() writes the env var and a jax config value: register
    both so teardown restores them."""
    monkeypatch.setenv(compile_cache.ENV, "")
    prev = jax.config.jax_compilation_cache_dir
    prev_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", prev)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      prev_secs)


def test_env_set_is_used_and_nothing_is_set_in_code(clean, tmp_path):
    clean.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == str(tmp_path)
    assert compile_cache.cache_dir() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # untouched
    assert os.environ[compile_cache.ENV] == str(tmp_path)


def test_unset_means_checkout_dot_jax_cache_and_children_inherit(clean):
    clean.delenv(compile_cache.ENV)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure() == want
    assert jax.config.jax_compilation_cache_dir == want
    # exported, so a child that never calls configure() lands there too
    assert os.environ[compile_cache.ENV] == want
    assert compile_cache.stats()["dir"] == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_cache_path_is_built_from_a_temp_name_pid_or_time():
    """Every place the repo names a compile-cache directory goes
    through the helper: nothing else sets the variable or the config
    option (the path is part of what makes a cache hit)."""
    hits = []
    for root, dirs, files in os.walk(REPO):
        # hidden directories are not the program: .git, the caches, a
        # builder's unpacked copy of the tree
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        for name in files:
            if not name.endswith((".py", ".sh")):
                continue
            path = os.path.join(root, name)
            if path in (compile_cache.__file__, __file__):
                continue
            with open(path, errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if re.search(r"jax_compilation_cache_dir|"
                                 r"JAX_COMPILATION_CACHE_DIR\W*=", line):
                        hits.append(f"{path}:{i}: {line.strip()}")
    assert not hits, hits


def test_hits_and_misses_are_counted_per_process(tmp_path):
    """A fresh directory misses, a second process hits — counted from
    jax.monitoring's compilation-cache events — though the program
    compiles in well under the second below which JAX alone would not
    keep it: configure() keeps everything."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from paddle_tpu.utils import compile_cache\n"
        "compile_cache.configure()\n"
        "import jax, jax.numpy as jnp\n"
        "jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))).block_until_ready()\n"
        "s = compile_cache.stats(); print(s['hits'], s['misses'], s['dir'])\n"
        % REPO)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    runs = [subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
            for _ in range(2)]
    for r in runs:
        assert r.returncode == 0, r.stderr[-2000:]
    (h1, m1, d1), (h2, m2, d2) = (r.stdout.split() for r in runs)
    assert d1 == d2 == str(tmp_path)
    assert int(h1) == 0 and int(m1) >= 1
    assert int(h2) >= 1 and os.listdir(tmp_path)
