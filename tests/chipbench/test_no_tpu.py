"""Without the chip the benchmark refuses: non-zero, one line, no
result (as ``tests/test_chip_smoke.py`` pins for the smoke)."""

import json
import os
import shutil
import subprocess
import sys

from chipbench import harness


def run_cmd(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    man = harness.manifest()
    argv = [sys.executable] + man["command"][1:] + [
        "--workload", man["workloads"][0]["name"], "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]
    return subprocess.run(argv, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_with_one_line(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    p = run_cmd(harness.ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""               # no result line
    said = [l for l in p.stderr.splitlines() if l.startswith("chipbench:")]
    assert len(said) == 1 and "TPU" in said[0] and "cpu" in said[0]


def test_alone_in_a_directory_it_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("work", "__pycache__"))
    p = run_cmd(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "paddle_tpu" in p.stderr


def test_the_generator_never_imports_jax():
    code = ("import sys; sys.argv=['loadgen']; "
            "import importlib.util as u; "
            "s=u.spec_from_file_location('lg', 'chipbench/loadgen.py'); "
            "m=u.module_from_spec(s); s.loader.exec_module(m); "
            "g=m.load_generator('sessions'); "
            "print(json.dumps('jax' in sys.modules or 'numpy' in "
            "sys.modules))")
    p = subprocess.run([sys.executable, "-c", "import json; " + code],
                       cwd=harness.ROOT, capture_output=True, text=True,
                       timeout=60)
    assert p.returncode == 0, p.stderr
    assert json.loads(p.stdout) is False
