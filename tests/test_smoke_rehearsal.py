"""chip_smoke.py end to end in rehearsal mode: CPU, tiny sizes,
interpreted kernels. It exercises the script — every phase as its own
child, the serve/SIGTERM drain, the kernel-path and prefix-cache checks,
the greedy comparison against the PADDLE_TPU_PALLAS=off artifact, both
trainers, and with --chips 4 the pinned four-replica fleet and the
data-parallel ZeRO-1 trainer — and says of itself that it is not
evidence about the chip. (About a minute per mode: sorted late so the
time-boxed tier-1 sweep spends its budget on the unit tests first.)"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_runs_every_phase_and_labels_itself(chips):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearsal", "--chips", str(chips)],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL") and "NOT evidence" in lines[0]
    last = json.loads(lines[-1])
    assert last == {"ok": True, "rehearsal": True, "evidence": False,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    out = r.stdout
    if chips == 1:
        for tag in ("cold", "warm"):
            assert f"serve[{tag}]: 9 requests answered" in out
            assert "all pallas_interpret" in out
        assert "serve[off]: 9 requests answered" in out and "all xla" in out
        assert "kernels vs PADDLE_TPU_PALLAS=off: 6 greedy rows " \
               "identical ids" in out
        assert "phase train_resnet: ok" in out
        assert "phase train_lm: ok" in out
    else:
        assert "batch shards on devices [0, 1, 2, 3]" in out
        assert "optimizer-state shards on [0, 1, 2, 3]" in out
        assert "answered by ['replica0', 'replica1', 'replica2', " \
               "'replica3'] on chips ['0', '1', '2', '3']" in out
