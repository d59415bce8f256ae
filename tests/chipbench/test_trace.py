"""The reduction from trace to numbers, on a hand-made trace and on a
small slice recorded on the chip (one decode step of the 1.3B cell)."""

import json
import os

import pytest

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def ev(name, start_us, dur_us, module=""):
    return [name, int(start_us * 1000), int(dur_us * 1000), module]


def planes():
    ops = [ev("%a = f32[] fusion(x)", 0, 100), ev("%b = f32[] copy(x)", 50, 100),
           ev("%a = f32[] fusion(x)", 300, 100),
           ev("%k = f32[] custom-call(x), custom_call_target=\"tpu_custom_call\"",
              320, 40)]
    mods = [ev("jit_step(11)", 0, 150), ev("jit_step(11)", 300, 100),
            ev("jit_other(22)", 600, 50)]
    host = [ev("np.asarray(jax.Array)", 140, 170), ev("sleep", 410, 100)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]},
        {"name": "/device:CUSTOM:Megascale Trace", "lines": []}]


def test_union_of_overlapping_intervals():
    assert trace.union_seconds([(0, 10), (5, 20), (30, 40)]) == \
        pytest.approx(30e-9)
    assert trace.union_seconds([]) == 0.0
    assert trace.union_seconds([(0, 10), (2, 3)]) == pytest.approx(10e-9)


def test_busy_is_the_union_and_idle_is_the_rest():
    r = trace.reduce_planes(planes(), 1)
    # ops cover [0,150) and [300,400): 250 us
    assert r["busy_s"] == pytest.approx(250e-6)
    assert r["chips_traced"] == 1


def test_device_time_per_name_and_per_program():
    r = trace.reduce_planes(planes(), 1)
    assert r["ops"]["%a fusion"] == [pytest.approx(200e-6), 2]
    assert r["ops"]["%k custom-call tpu_custom_call"][1] == 1
    assert r["modules"]["jit_step(11)"] == [pytest.approx(250e-6), 2]
    assert r["modules"]["jit_other(22)"][1] == 1
    # ops without a module stat fall to the program that covers them
    assert set(r["ops_by_module"]) == {"jit_step(11)"}
    assert r["ops_by_module"]["jit_step(11)"]["%b copy"][1] == 1
    top = r["breakdown"]["device_ops"]
    assert top[0][0] == "%a fusion" and len(top) <= 10


def test_idle_gaps_are_named_by_what_the_host_did():
    r = trace.reduce_planes(planes(), 1)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["host: np.asarray(jax.Array)",
                       pytest.approx(150e-6)]


def test_op_label_and_module_base():
    line = ("%while.5 = (s32[]{:T(128)}, bf16[8,2048]{1,0:T(8,128)(2,1)S(1)})"
            " while((s32[]{:T(128)}) %tuple.34), condition=%c, body=%b")
    assert trace.op_label(line) == "%while.5 while"
    assert trace.module_base("jit_call_exported(909909)") == \
        "jit_call_exported"


def test_recorded_decode_step_of_the_1p3b_cell():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        rec = json.load(f)
    r = trace.reduce_planes(rec, 1)
    (name, (secs, n)), = r["modules"].items()
    assert trace.module_base(name) == "jit_call_exported" and n == 1
    assert secs == pytest.approx(0.0588678, rel=1e-3)       # 58.9 ms a step
    assert r["busy_s"] <= secs * 1.001
    assert r["busy_s"] == pytest.approx(secs, rel=0.01)
    labels = list(r["ops"])
    assert any(l.endswith("custom-call tpu_custom_call") for l in labels)
    assert any(l.startswith("%while") for l in labels)
    assert all(len(n) < 90 for n, _ in r["breakdown"]["device_ops"])
