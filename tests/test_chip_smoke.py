"""chip_smoke.py's contract, the parts a CPU can check quickly: without
a TPU it exits non-zero and prints no result (unless the rehearsal flag
is given — the end-to-end rehearsal is tests/test_smoke_rehearsal.py);
its parent process never imports JAX; alone in a directory it fails."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd, **env):
    return subprocess.run(
        [sys.executable] + argv, cwd=cwd, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    r = _run([SMOKE], REPO)
    assert r.returncode != 0
    assert "JAX found no TPU" in r.stderr
    assert '"ok"' not in r.stdout
    # the refusal comes from the first child's own device report
    assert "'platform': 'cpu'" in r.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([str(tmp_path / "chip_smoke.py")], str(tmp_path),
             PYTHONPATH="")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "No module named 'paddle_tpu'" in r.stderr


def test_parent_never_imports_jax_and_requests_follow_the_spec():
    """Import the script the way its parent runs (no --phase): JAX must
    not come with it; the seeded request set has >= 8 requests, a
    prompt past half the cache, two sharing a >= 256-token prefix, and
    both greedy and temperature/top-k rows."""
    code = (
        "import json, random, sys\n"
        "sys.path.insert(0, %r)\n"
        "import chip_smoke as cs\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
        "reqs, shared = cs.make_requests(cs.LM_FULL, random.Random(cs.SEED))\n"
        "print(json.dumps({'n': len(reqs), 'shared': shared,\n"
        "  'lens': [len(r['prompt']) for r in reqs],\n"
        "  'greedy': sum(1 for r in reqs if not r.get('temperature')),\n"
        "  'topk': sum(1 for r in reqs if r.get('top_k')),\n"
        "  'same': reqs[1]['prompt'][:shared] == reqs[4]['prompt'][:shared],\n"
        "  'lm': {k: cs.LM_FULL[k] for k in ('vocab', 'd_model', 'n_heads',\n"
        "         'n_layers', 'd_ff', 'slots', 'cache_len')}}))\n" % REPO)
    r = _run(["-c", code], REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(r.stdout)
    assert doc["n"] >= 8 and max(doc["lens"]) >= 1024
    assert doc["shared"] >= 256 and doc["same"]
    assert doc["greedy"] >= 4 and doc["topk"] >= 2
    assert doc["lm"] == {"vocab": 32000, "d_model": 512, "n_heads": 8,
                         "n_layers": 6, "d_ff": 2048, "slots": 8,
                         "cache_len": 2048}
