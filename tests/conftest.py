"""Test config: force an 8-device virtual CPU mesh before the JAX backend
initialises.

Mirrors the reference's strategy of running distributed tests without a real
cluster (SURVEY.md §4.6 — in-process pservers); on TPU the analog is a
host-simulated multi-device mesh. The tests never reach for a chip: the
platform is forced to CPU in-process, whatever ``JAX_PLATFORMS`` says.
"""

import os

os.environ.setdefault("PADDLE_TPU_SEED", "42")
# keep tests fp32-exact on CPU: matmuls would otherwise downcast to bf16
os.environ.setdefault("PADDLE_TPU_COMPUTE_DTYPE", "float32")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests (multi-process clusters etc.)")


@pytest.fixture
def rng():
    return np.random.RandomState(1234)


@pytest.fixture(autouse=True)
def _pipeline_thread_leak_guard():
    """Fail any test that leaves input-pipeline or reader worker threads
    alive: every pipeline/reader thread is named with a ``pipeline-`` /
    ``reader-`` prefix and must be joined by ``close()`` or generator
    close. The gc.collect() first closes abandoned reader generators
    deterministically (their close handlers join the workers); a short
    grace loop absorbs threads that are mid-exit."""
    yield
    import gc
    import threading
    import time

    def leaked():
        return [t.name for t in threading.enumerate()
                if t.is_alive()
                and t.name.startswith(("pipeline-", "reader-"))]

    if not leaked():
        return
    gc.collect()
    deadline = time.time() + 3.0
    names = leaked()
    while names and time.time() < deadline:
        time.sleep(0.05)
        names = leaked()
    assert not names, (
        f"test leaked live pipeline/reader threads: {names} — close() "
        f"the pipeline or exhaust/close the reader generator")
