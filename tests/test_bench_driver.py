"""bench.py is a straight-line, single-process measurement: it asserts
a TPU, takes its peak from the device-kind table (unknown kind = error,
the nominal CPU entry never reaches a printed number), times work that
ends in ``block_until_ready`` and fails — never retries, never prints an
older number — when something is wrong."""

import os
import sys
import types

import numpy as np
import pytest

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402
from paddle_tpu.core import place  # noqa: E402
from paddle_tpu.utils import sync  # noqa: E402


def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_refuses_to_measure_anything_but_a_tpu(capsys, monkeypatch):
    """On this CPU host the bench exits non-zero before building
    anything and prints NO result line: a CPU timing is not a device
    metric."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    with pytest.raises(SystemExit) as e:
        bench.main([])
    assert e.value.code not in (0, None)
    assert "measures a TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_peak_comes_from_the_table_and_unknown_kind_raises(monkeypatch):
    assert place.peak_flops(_dev("tpu", "TPU v5 lite"),
                            required=True) == 197e12
    with pytest.raises(ValueError, match="(?i)tpu v99"):
        place.peak_flops(_dev("tpu", "TPU v99"), required=True)
    # the nominal cpu entry serves the MFU plumbing in CPU tests only
    assert place.peak_flops(_dev("cpu", "cpu")) == 0.1e12
    with pytest.raises(ValueError, match="cpu"):
        place.peak_flops(_dev("cpu", "cpu"), required=True)
    # an env override cannot stand in for the table on the bench path
    monkeypatch.setenv("PADDLE_TPU_PEAK_TFLOPS", "1234")
    assert place.peak_flops(_dev("tpu", "TPU v99")) == 1234e12
    with pytest.raises(ValueError):
        place.peak_flops(_dev("tpu", "TPU v99"), required=True)


def test_host_sync_blocks_and_returns_the_callers_scalar():
    tree = {"w": jnp.ones((8, 8)) * 3}
    loss = jnp.asarray(2.5)
    assert sync.host_sync(tree, loss) == 2.5
    assert sync.host_sync(tree) == 0.0
    # no extra device op of its own: the jaxpr of a jitted caller is
    # untouched because host_sync never traces
    assert "jnp.sum" not in open(sync.__file__).read()


def test_no_probe_loop_stale_value_or_assumed_peak_survives():
    src = open(os.path.join(REPO, "bench.py")).read()
    for gone in ("last_verified", "stale", "PLAUSIBLE_MAX", "Watchdog",
                 "orchestrate", "--probe", "--child", "subprocess",
                 "PEAK_TFLOPS", "BENCH_TRY_MODES", "BENCH_PLATFORM",
                 "except Exception", "os._exit"):
        assert gone not in src, gone
    assert "required=True" in src and "block_until_ready" in \
        open(sync.__file__).read()


def test_timed_window_reports_rate_and_fails_on_a_nonfinite_loss():
    """bench_batch on a stand-in step: the rate is batch / mean step
    time, set-up time is reported apart, and a NaN loss raises instead
    of being printed as a throughput."""
    calls = []

    def step(p, o, s, images, labels, i):
        calls.append(int(i))
        return jnp.asarray(1.0), p, o, s

    ips, setup_s, carry = bench.bench_batch(step, (1, 2, 3), batch=4,
                                            warmup=1, iters=3)
    assert len(calls) == 4 and carry == (1, 2, 3)
    assert ips > 0 and setup_s >= 0 and np.isfinite(ips)

    def bad(p, o, s, images, labels, i):
        return jnp.asarray(float("nan")), p, o, s

    with pytest.raises(FloatingPointError, match="loss"):
        bench.bench_batch(bad, (1, 2, 3), batch=4, warmup=1, iters=1)


def test_resnet50_recipe_is_the_one_chip_smoke_trains():
    cost = bench.resnet50_cost(stem_s2d=True, fused_bn=False)
    from paddle_tpu.topology import Topology
    specs = Topology(cost).param_specs()
    n = sum(int(np.prod(s.shape)) for s in specs)
    assert 25.4e6 < n < 25.7e6          # ResNet-50: 25.6M parameters
    assert "bench.resnet50_cost" in open(
        os.path.join(REPO, "chip_smoke.py")).read()
