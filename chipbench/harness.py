"""What every cell shares: the manifest, the device, the peaks table,
the per-layer metric readers, the profiler window and the result line.

Driven by data: a cell is found by name in ``BENCHMARK.json``; its
configuration (``configs/<name>.json``) names the system that runs it
(``systems/<system>.py``), its traffic file (``traffic/<name>.json``)
names the generator, each per-layer metric has a reader of its own
(``metrics/<name>.py``, see ``reader_path``), and the limits of the output check sit in
``limits/<workload>.json``. Adding any of these adds files and manifest
entries and edits nothing here.
"""

import glob
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(HERE, "work")            # chipbench/.gitignore lists it


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with the files its names lead to."""

    @classmethod
    def from_parts(cls, name: str, config: dict, traffic_file: str,
                   limits: dict, man: dict, chips: int = 1):
        """A cell put together by hand (the CPU tests' tiny cells)."""
        self = cls.__new__(cls)
        self.name, self.chips = name, chips
        self.row = {"name": name, "chips": chips}
        self.config, self.config_name = config, config["name"]
        self.traffic_file = traffic_file
        self.traffic = load_json(traffic_file)
        self.limits = limits
        self.end_to_end = [m for m in man["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in man["per_layer"]
                          if name in m.get("workloads", [name])]
        return self

    def __init__(self, name: str, man: dict = None, root: str = ROOT):
        man = man or manifest()
        rows = [w for w in man["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(f"chipbench: no workload {name!r} in "
                             f"BENCHMARK.json")
        self.name = name
        self.row = rows[0]
        self.chips = int(self.row["chips"])
        cfg_row = [c for c in man["configs"]
                   if c["name"] == self.row["config"]][0]
        self.config = load_json(os.path.join(root, cfg_row["file"]))
        self.config_name = cfg_row["name"]
        self.traffic_file = os.path.join(
            HERE, "traffic", f"{self.row['traffic']}.json")
        self.traffic = load_json(self.traffic_file)
        limits = os.path.join(HERE, "limits", f"{name}.json")
        self.limits = load_json(limits) if os.path.exists(limits) else {}
        self.end_to_end = [
            m for m in man["end_to_end"]
            if "workloads" not in m or name in m["workloads"]]
        self.per_layer = [
            m for m in man["per_layer"]
            if "workloads" not in m or name in m["workloads"]]


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(f"chipbench: no published peaks for device kind "
                         f"{device_kind!r} in chipbench/peaks.json; a "
                         f"device that is not in the table is an error")
    return table[device_kind]


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it, or the end of the run: a cell is
    measured on the TPU it asks for and never anywhere else."""
    info = device_info()
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"chipbench: this cell needs {chips} TPU chip(s); JAX found "
              f"{info}. Nothing is measured off the chip and no result "
              f"is printed.", file=sys.stderr, flush=True)
        raise SystemExit(3)
    return info


def device_info() -> dict:
    """The device as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """The peak on the fullest chip, as JAX reports it: the allocator's
    ``peak_bytes_in_use`` (arguments, outputs, live arrays) plus
    ``peak_bytes_reserved``, which is where a TPU keeps the running
    program's temporaries. ``peak_bytes_in_use`` alone reads 0.87 GB for
    a ResNet-50 step whose activations take 8.8 GB (PR 23)."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


# -- the profiler window ------------------------------------------------------

class TraceWindow:
    """``jax.profiler`` around a stretch of the measured window; the
    trace is read back with ``jax.profiler.ProfileData`` and deleted."""

    def __init__(self, keep: bool = False):
        self.dir = os.path.join(WORK, "trace")
        self.keep = keep
        self.t_start = self.t_stop = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.time()

    def stop(self):
        import jax
        self.t_stop = time.time()       # stopping gathers for seconds
        jax.profiler.stop_trace()

    def read(self, chips: int) -> dict:
        from chipbench import trace as _trace
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"no xplane file under {self.dir}")
        summary = _trace.reduce_file(files[0], chips)
        summary["window_s"] = self.t_stop - self.t_start
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        return summary


# -- per-layer metric readers -------------------------------------------------

def reader_path(name: str) -> str:
    """``metrics/<name>.py``; a quantity split by the end-to-end metric
    it moves (``device_idle_pct.tok``, ``device_idle_pct.train``) may
    share the reader named by the part before the first dot."""
    own = os.path.join(HERE, "metrics", f"{name}.py")
    if os.path.exists(own) or "." not in name:
        return own
    return os.path.join(HERE, "metrics", f"{name.split('.', 1)[0]}.py")


def read_per_layer(cell: Cell, ctx: dict) -> dict:
    """Run each of the cell's per-layer readers over ``ctx`` (counters,
    spans, the trace summary, the cell's shapes). A reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(reader_path(m["name"]), "chipbench_metric_" +
                          m["name"].replace(".", "_").replace("-", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- the result line ----------------------------------------------------------

def finish(cell: Cell, *, trace: bool, correct: bool, attempted: int,
           failed: int, end_to_end: dict, per_layer: dict, device: dict,
           compared: dict, breakdown: dict = None, notes: dict = None):
    """Print the compared numbers beside their limits (stderr, last
    lines) and the one result object (stdout, last line)."""
    if trace:
        metrics = per_layer
    else:
        metrics = {m["name"]: {"value": float(end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    doc = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace and breakdown:
        doc["breakdown"] = breakdown
    if notes:
        doc["notes"] = notes
    doc["compared"] = compared
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(doc), flush=True)
