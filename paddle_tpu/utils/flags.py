"""Global runtime flags registry.

Reference: paddle/utils/Flags.cpp:18-81 centralises every runtime knob as a
gflag (use_gpu, trainer_count, port, trainer_id, num_gradient_servers,
parallel_nn, beam_size, ...). Here flags are a typed registry usable from
Python and settable via paddle_tpu.init(**kwargs) or environment variables
(PADDLE_TPU_<NAME>).
"""

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


@dataclass
class _FlagSpec:
    name: str
    default: Any
    help: str
    parser: Callable[[str], Any]


def _parse_bool(s):
    if isinstance(s, bool):
        return s
    return str(s).lower() in ("1", "true", "yes", "on")


class FlagRegistry:
    """Typed flag registry with env-var overrides (PADDLE_TPU_<NAME>)."""

    def __init__(self):
        self._specs: Dict[str, _FlagSpec] = {}
        self._values: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def define(self, name: str, default: Any, help: str = "",
               parser: Optional[Callable] = None):
        if parser is None:
            if isinstance(default, bool):
                parser = _parse_bool
            elif isinstance(default, int):
                parser = int
            elif isinstance(default, float):
                parser = float
            else:
                parser = str
        with self._lock:
            self._specs[name] = _FlagSpec(name, default, help, parser)
            env = os.environ.get("PADDLE_TPU_" + name.upper())
            self._values[name] = parser(env) if env is not None else default
        return self

    def __getattr__(self, name):
        # only called when normal attribute lookup fails
        values = self.__dict__.get("_values", {})
        if name in values:
            return values[name]
        raise AttributeError(f"unknown flag {name!r}")

    def get(self, name, default=None):
        return self._values.get(name, default)

    def set(self, name, value):
        with self._lock:
            if name not in self._specs:
                raise KeyError(f"unknown flag {name!r}")
            spec = self._specs[name]
            self._values[name] = spec.parser(value) if isinstance(value, str) else value

    def set_if_known(self, name, value):
        """Silently ignore unknown flags — paddle.init() historically accepted
        arbitrary gflags (python/paddle/v2/__init__.py:123)."""
        if name in self._specs:
            self.set(name, value)

    def describe(self):
        return {n: (self._values[n], s.help) for n, s in self._specs.items()}


GLOBAL_FLAGS = FlagRegistry()

# Mirrors of the reference's core flags (paddle/utils/Flags.cpp) that still
# make sense on TPU, plus TPU-native additions.
GLOBAL_FLAGS.define("use_tpu", True, "prefer TPU devices when present (was: use_gpu)")
GLOBAL_FLAGS.define("trainer_count", 1, "data-parallel shards on the local mesh")
GLOBAL_FLAGS.define("trainer_id", 0, "distributed trainer index")
GLOBAL_FLAGS.define("seed", 0, "global RNG seed; 0 derives from time")
GLOBAL_FLAGS.define("log_period", 100, "batches between metric log lines")
GLOBAL_FLAGS.define("test_period", 0, "batches between mid-pass tests (0=off)")
GLOBAL_FLAGS.define("beam_size", 7, "default beam width for sequence generation")
GLOBAL_FLAGS.define("show_layer_stat", False, "print per-layer stats each batch")
GLOBAL_FLAGS.define("enable_x64", False, "enable float64/int64 (jax_enable_x64)")
GLOBAL_FLAGS.define("default_dtype", "float32", "parameter dtype")
GLOBAL_FLAGS.define("compute_dtype", "bfloat16", "matmul/conv compute dtype on TPU")
GLOBAL_FLAGS.define("profile", False, "emit jax.profiler traces around hot loops")
GLOBAL_FLAGS.define("debug_nans", False,
                    "trap NaNs: re-run jitted code op-by-op and raise at the "
                    "producing op (was: feenableexcept FE_INVALID, "
                    "TrainerMain.cpp:49)")
GLOBAL_FLAGS.define("debug_infs", False,
                    "trap Infs like debug_nans (was: feenableexcept "
                    "FE_OVERFLOW|FE_DIVBYZERO)")
GLOBAL_FLAGS.define("checkpoint_period", 0, "batches between async checkpoints (0=per pass)")
GLOBAL_FLAGS.define("metrics_path", "", "JSONL per-step metrics file (also: "
                    "PADDLE_TPU_METRICS_PATH); empty = off")
GLOBAL_FLAGS.define("flight_dir", "", "directory for flight-recorder "
                    "post-mortem artifacts (also: PADDLE_TPU_FLIGHT_DIR); "
                    "empty = working directory, and crash dumps beyond the "
                    "NaN tripwire stay off")
