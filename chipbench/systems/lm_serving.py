"""Serving cells: the program's paged engine behind its TCP replica
wire, in the one process that holds the chip.

The path is the one ``python -m paddle_tpu serve --port`` runs:
``io.lm_serving.save_lm_artifact(engine_paged=True)`` (once per
checkout, geometry and state of the program's source),
``load_lm_artifact(path)``, ``.engine()``, ``precompile()``,
``serving.replica.ReplicaServer.serve_forever()`` on the main thread. The benchmark's own process stands around it because
only the process that holds the chip can trace it. Load comes from
``loadgen.py``, a child that never imports JAX.

One departure, named in the configuration's ``departures``: the weights
the engine is handed are the seed's, made on the device by
``chipbench.weights`` and set as ``LMServer.params``. The artifact's own
``params.npz`` is read (its cost is in ``setup_s``) and dropped.
"""

import gc
import hashlib
import json
import os
import subprocess
import sys
import threading
import time

from chipbench import compare, flops, harness, reduce, weights


def transformer_config(cfg: dict):
    import jax.numpy as jnp
    from paddle_tpu.models import transformer
    d = flops.lm_dims(cfg)
    return transformer.TransformerConfig(
        vocab=d["vocab"], d_model=d["d_model"], n_heads=d["n_heads"],
        n_layers=d["n_layers"], d_ff=d["d_ff"], max_len=d["max_len"],
        dtype=jnp.dtype(cfg["compute_dtype"]))


def program_sources_hash(root: str = None) -> str:
    """One hash over every ``paddle_tpu/**/*.py`` (path and bytes): the
    exported programs are the program's source frozen at export, so an
    artifact is reused only by the source that made it. A working tree
    in which the model, the sampler or a kernel was edited exports
    anew; it never times its parent's programs."""
    root = root or os.path.join(harness.ROOT, "paddle_tpu")
    h = hashlib.sha256()
    for d, dirs, files in os.walk(root):
        dirs.sort()
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(d, name)
            h.update(os.path.relpath(path, root).encode() + b"\0")
            with open(path, "rb") as f:
                h.update(f.read())
            h.update(b"\0")
    return h.hexdigest()


def artifact_path(cell, work: str) -> str:
    import jax
    key = json.dumps([flops.lm_dims(cell.config), cell.config["serving"],
                      cell.config["compute_dtype"], jax.__version__,
                      jax.default_backend(), program_sources_hash()],
                     sort_keys=True)
    h = hashlib.sha1(key.encode()).hexdigest()[:12]
    return os.path.join(work, cell.config_name, f"artifact-{h}.tar")


def ensure_artifact(cell, params, work: str) -> tuple:
    """Export once per checkout, geometry and program source; at most
    one file kept."""
    from paddle_tpu.io import lm_serving
    path = artifact_path(cell, work)
    if os.path.exists(path):
        return path, False
    d = os.path.dirname(path)
    os.makedirs(d, exist_ok=True)
    for old in os.listdir(d):
        os.remove(os.path.join(d, old))
    sv = cell.config["serving"]
    tmp = path + ".part"
    lm_serving.save_lm_artifact(
        tmp, params, transformer_config(cell.config), batch=sv["slots"],
        prompt_len=8, cache_len=sv["cache_len"],
        engine_buckets=tuple(sv["chunk_buckets"]), engine_paged=True,
        engine_block_size=sv["block_size"],
        engine_num_blocks=sv["num_blocks"])
    os.replace(tmp, path)
    return path, True


def flat_counters(registry) -> dict:
    """The engine registry as name -> number: counters and gauges summed
    over their labels, histograms as ``<name>_sum`` / ``<name>_count``."""
    out = {}
    for name, doc in registry.snapshot().items():
        if doc["kind"] == "histogram":
            out[name + "_sum"] = sum(s["sum"] for s in doc["series"])
            out[name + "_count"] = sum(s["count"] for s in doc["series"])
        else:
            out[name] = sum(s["value"] for s in doc["series"])
    return out


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a.get(k, 0.0) for k in b}


def check_kernel_paths(eng, want: str):
    paths = eng.kernel_paths or {}
    placed = {v for rec in paths.values() for v in rec.values()}
    sites = {s for rec in paths.values() for s in rec}
    if placed != {want} or not {"attention", "span_write",
                                "sampler"} <= sites or "decode" not in paths:
        raise SystemExit(f"chipbench: the engine's programs placed {paths}; "
                         f"the configuration asks for {want!r} at every "
                         f"kernel site")


def build(cell, seed: int, work: str):
    """Set-up up to a warm engine: (server object, engine, spans)."""
    from paddle_tpu.io import lm_serving
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    mode = cell.config["serving"].get("pallas")
    if mode:        # the policy the artifact's programs are exported under
        os.environ["PADDLE_TPU_PALLAS"] = mode
    dims = flops.lm_dims(cell.config)
    spans = {}
    t = time.time()
    params = weights.lm_weights(seed, dims)
    path, exported = ensure_artifact(cell, params, work)
    spans["export_s"] = time.time() - t if exported else 0.0
    t_load = time.time()
    srv = lm_serving.load_lm_artifact(path)
    spans["artifact_load_s"] = time.time() - t_load
    srv.params = params
    eng = srv.engine(seed=int(seed) % (2 ** 31))
    eng.precompile()
    spans["replica_ready_s"] = time.time() - t_load
    spans["compile_cache"] = compile_cache.stats()
    check_kernel_paths(eng, cell.config["serving"]["kernel_paths"])
    return srv, eng, spans


def drive(cell, eng, seed: int, seconds: float, tracer=None):
    """One window: the replica server on this thread, the generator as a
    child, counters snapshot at the window's edges (and the traced
    stretch's). Returns the generator's final document and snapshots."""
    from paddle_tpu.serving import replica
    server = replica.ReplicaServer(eng, host="127.0.0.1", port=0)
    dims = flops.lm_dims(cell.config)
    argv = [sys.executable, os.path.join(harness.HERE, "loadgen.py"),
            "--port", str(server.port), "--traffic", cell.traffic_file,
            "--seed", str(seed), "--seconds", str(seconds),
            "--vocab", str(dims["vocab"])]
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE, text=True,
                            cwd=harness.ROOT)
    box = {"snaps": {}}
    trace_s = float(cell.traffic.get("trace_s", 3))

    def stop_trace():
        box["snaps"]["trace_stop"] = flat_counters(eng.metrics)
        tracer.stop()

    def monitor():
        try:
            for line in proc.stdout:
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
                ev = doc.get("event")
                if ev == "open":
                    box["snaps"]["open"] = flat_counters(eng.metrics)
                    box["open_wall"] = doc["wall"]
                    if tracer is not None:
                        tracer.start()
                        box["snaps"]["trace_start"] = \
                            flat_counters(eng.metrics)
                        timer = threading.Timer(
                            min(trace_s, seconds), stop_trace)
                        timer.daemon = True
                        timer.start()
                        box["timer"] = timer
                elif ev == "close":
                    box["snaps"]["close"] = flat_counters(eng.metrics)
                elif ev == "done":
                    box["final"] = doc
        finally:
            server.drain()

    th = threading.Thread(target=monitor, name="chipbench-monitor",
                          daemon=True)
    th.start()
    try:
        server.serve_forever()
    finally:
        if proc.poll() is None and "final" not in box:
            proc.kill()
        proc.wait()
        th.join(timeout=30)
        if "timer" in box:
            box["timer"].join(timeout=30)
        server.close()
    if "final" not in box:
        raise RuntimeError(f"the load generator ended (code "
                           f"{proc.returncode}) without its document")
    return box


def end_to_end(final: dict) -> dict:
    recs, a, b = final["records"], final["t_open"], final["t_close"]
    out = {"serve_tok_s": reduce.serve_tok_s(recs, a, b)}
    ttft = reduce.ttft_ms_all(recs, a, b)
    tpot = reduce.tpot_ms_all(recs, a, b)
    if ttft:
        out["ttft_p95_ms"] = reduce.percentile(ttft, 95)
        out["ttft_p50_ms"] = reduce.percentile(ttft, 50)
    if tpot:
        out["tpot_p95_ms"] = reduce.percentile(tpot, 95)
    return out


def counts(final: dict) -> tuple:
    """(attempted, failed, never answered) over the requests the window
    is judged on: those sent or due before the close."""
    recs = final["records"]
    failed = sum(1 for r in recs if r.get("error"))
    return len(recs), failed + final["unanswered"], final["unanswered"]


def check(cell, seed: int, final: dict, control: str = None) -> dict:
    """The reference over the sample, on weights made again from the
    seed (the program's are freed by now)."""
    dims = flops.lm_dims(cell.config)
    w = weights.lm_weights(seed, dims)
    return compare.served_gaps(w, final["sample"], dims, control=control)


def run(cell, *, seed, seconds, trace, device, t_start, keep_trace=False,
        work: str = None, break_engine=None) -> int:
    """``break_engine`` is the tests' hook: it gets the warm engine and
    breaks the timed path underneath the run."""
    work = work or harness.WORK
    srv, eng, spans = build(cell, seed, work)
    if break_engine is not None:
        break_engine(eng)
    tracer = harness.TraceWindow(keep=keep_trace) if trace else None
    box = drive(cell, eng, seed, seconds, tracer)
    final = box["final"]
    setup_s = box["open_wall"] - t_start
    e2e = end_to_end(final)
    e2e["setup_s"] = setup_s
    attempted, failed, unanswered = counts(final)
    mem = harness.memory_peak_bytes(cell.chips)
    dims = flops.lm_dims(cell.config)
    ctx = None
    if trace:
        summary = tracer.read(cell.chips)
        ctx = {"cell": cell, "dims": dims, "spans": spans,
               "peaks": harness.peaks_for(device["kind"]),
               "counters": delta(box["snaps"]["open"], box["snaps"]["close"]),
               "traced_counters": delta(box["snaps"]["trace_start"],
                                        box["snaps"]["trace_stop"]),
               "trace": summary, "records": final["records"],
               "traced_interval": traced_interval(final, box, tracer),
               "kv_bytes_per_token": eng.kv_bytes_per_token,
               "slots": eng.batch, "block_size": eng.block_size}
    # free the program's state before the reference touches the chip
    del srv.params
    eng.params = eng.cache = None
    del srv, eng
    gc.collect()
    got = check(cell, seed, final)
    values = {"unanswered": unanswered}
    if got["tokens_compared"] > 0:      # nothing compared is not correct
        values["gap_max"] = got["gap_max"]
    compared = compare.judge(values, cell.limits)
    correct = all(c["ok"] for c in compared.values()) and bool(compared)
    dev = dict(device, memory_peak_bytes=mem)
    per_layer, breakdown = {}, None
    if trace:
        dev["busy_s"] = ctx["trace"]["busy_s"]
        dev["window_s"] = ctx["trace"]["window_s"]
        per_layer = harness.read_per_layer(cell, ctx)
        breakdown = ctx["trace"]["breakdown"]
    notes = {"generator_late_ms": final["generator_late_ms"],
             "requests": attempted, "tokens_compared": got["tokens_compared"],
             "tokens_off_best": got["tokens_off_best"],
             "compared_requests": got["requests"],
             "engine_tokens_in_window": delta(
                 box["snaps"]["open"], box["snaps"]["close"]).get(
                 "engine_tokens_total"),
             "spans": {k: v for k, v in spans.items()},
             "ttft_p50_ms": e2e.get("ttft_p50_ms")}
    harness.finish(cell, trace=trace, correct=correct, attempted=attempted,
                   failed=failed, end_to_end=e2e, per_layer=per_layer,
                   device=dev, compared=compared, breakdown=breakdown,
                   notes=notes)
    return 0


def traced_interval(final: dict, box: dict, tracer) -> tuple:
    """The traced stretch on the generator's clock."""
    off = final["t_open"] - box["open_wall"]       # wall -> generator
    return (tracer.t_start + off, tracer.t_stop + off)
