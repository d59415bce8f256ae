"""The benchmark's own CPU tests: the yardstick checked without a chip.

Nothing here describes a topology or touches a TPU at import; the
repo's ``tests/conftest.py`` has already forced the CPU platform.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="session")
def manifest():
    from chipbench import harness
    return harness.manifest()


def tiny_cell(name, config, traffic, limits, end_to_end=()):
    """A CPU-sized cell under a real cell's name (so that it reports that
    cell's metrics); ``end_to_end`` adds metrics the manifest has not got
    yet (the sessions cell's tails)."""
    from chipbench import harness
    cfg = harness.load_json(os.path.join(HERE, "configs", f"{config}.json"))
    man = dict(harness.manifest())
    man["end_to_end"] = list(man["end_to_end"]) + [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": [name]} for n in end_to_end]
    return harness.Cell.from_parts(
        name, cfg, os.path.join(HERE, "traffic", f"{traffic}.json"),
        limits, man)
