"""The committed limits against the committed readings they were set
from (``chipbench/limits/readings/<cell>.json``, read on the chip): every
sound run of the program is correct, every control and every planted
fault is not, under the names and limits the cells compare. No program
runs here: this is the arithmetic that decides ``correct``."""

import os

import numpy as np
import pytest

from chipbench import compare, harness

CELLS = [w["name"] for w in harness.manifest()["workloads"]]


def readings(cell: str) -> dict:
    return harness.load_json(os.path.join(
        harness.HERE, "limits", "readings", f"{cell}.json"))


def verdict(cell: str, row: dict) -> dict:
    return compare.judge(row, harness.Cell(cell).limits)


@pytest.mark.parametrize("cell", CELLS)
def test_every_sound_reading_is_correct(cell):
    rows = readings(cell)["program"]
    assert len(rows) >= 12                  # a dozen seeds or more
    for row in rows:
        doc = verdict(cell, row)
        assert doc and all(c["ok"] for c in doc.values()), (row["seed"], doc)


@pytest.mark.parametrize("cell", CELLS)
def test_every_control_and_fault_reading_is_not_correct(cell):
    r = readings(cell)
    kinds = {**r["controls"], **r["faults"]}
    assert "fp8" in kinds                   # the nearest precision below
    for kind, rows in kinds.items():
        assert rows, kind
        for row in rows:
            doc = verdict(cell, row)
            failed = [k for k, c in doc.items() if k in row and not c["ok"]]
            assert failed, (kind, row)      # on a number that was read


@pytest.mark.parametrize("cell", CELLS)
def test_each_limit_sits_between_its_two_readings(cell):
    """Above the largest sound reading with room, and under the smallest
    reading of whatever it is there to catch (its ``upper``)."""
    r = readings(cell)
    for name, lim in harness.Cell(cell).limits.items():
        if "lower" not in lim:
            continue                        # a stated limit (unanswered: 0)
        sound = max(row[name] for row in r["program"])
        assert sound <= lim["lower"] < lim["limit"] < lim["upper"], name
        assert lim["upper"] >= 3 * lim["lower"], name
        assert lim["limit"] >= 1.5 * lim["lower"], name


def test_the_training_cell_compares_the_numbers_the_readings_name():
    lim = harness.Cell("resnet50.train-b256").limits
    assert set(lim) == {"grad_diff_rel_fc", "grad_norm_gap_median",
                        "change_norm_gap_median", "grad_norm_gap",
                        "change_norm_gap"}
    row = readings("resnet50.train-b256")["program"][0]
    assert set(lim) <= set(row)


def test_a_state_left_unchanged_reads_one_through_the_trainers_own_gaps():
    """The training numbers by ``systems/trainer.readings_gap`` on
    hand-made trees: a program whose state never moved (gradient and
    change all nought) reads 1 by the worst leaf and as a vector, and
    fails each committed limit; the reference against itself reads 0 and passes."""
    from chipbench.systems import trainer as tsys
    rng = np.random.default_rng(7)
    leaves = {"res_fc.w": (8, 4), "res5_0_a_conv.w": (1, 1, 8, 4),
              "res4_0_a_conv.w": (1, 1, 4, 4), "res2_0_a_conv.w": (1, 1, 4, 2),
              "res2_0_a_bn.gamma": (2,), "res_fc.b": (4,)}
    g = {k: rng.standard_normal(s).astype(np.float32)
         for k, s in leaves.items()}
    norms = {k: float(np.linalg.norm(v)) for k, v in g.items()}
    refr = {"losses": [2.0, 1.9, 1.8], "grad_norm": norms,
            "change_norm": {k: 0.03 * v for k, v in norms.items()},
            "grad_weights": {k: v for k, v in g.items() if k.endswith(".w")}}
    lim = harness.Cell("resnet50.train-b256").limits
    same = compare.judge(tsys.readings_gap(refr, refr), lim)
    assert all(c["ok"] and c["value"] == 0.0 for c in same.values())
    stuck = {"losses": refr["losses"],
             "grad_norm": {k: 0.0 for k in norms},
             "change_norm": {k: 0.0 for k in norms},
             "grad_weights": {k: np.zeros_like(v)
                              for k, v in refr["grad_weights"].items()}}
    doc = compare.judge(tsys.readings_gap(stuck, refr), lim)
    assert set(doc) == set(lim)
    assert not any(c["ok"] for c in doc.values()), doc
    for name in ("grad_norm_gap", "change_norm_gap", "grad_diff_rel_fc"):
        assert doc[name]["value"] == pytest.approx(1.0), name
