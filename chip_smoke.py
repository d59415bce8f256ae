#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the two hot paths once, through the entry points a user calls, on
one TPU chip: the paged LM server (``python -m paddle_tpu serve`` over an
``io.lm_serving`` artifact, kernels on) and the trainers
(``paddle.trainer.SGD.train`` on ResNet-50; the transformer LM's
flash-attention train step). Weights are random from a seed, depth is
cut, width is the widest the repo's records name
(``benchmarks/transformer_bench.py``: vocab 32000, d_model 512, 8 heads,
6 layers, bf16; ResNet-50 at 224x224, batch 128).

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: DP training, 4 replicas
    python chip_smoke.py --rehearsal  # CPU, tiny, interpreted kernels:
                                      # exercises this script, proves nothing

The parent NEVER imports JAX: a chip belongs to one process at a time, so
every phase is its own child process, run one after another and waited
for. Every child names the device JAX gave it; any platform but ``tpu``
fails the run (the rehearsal flag aside). Nothing is caught and carried
past: a phase that fails ends the run with a non-zero exit code and no
result line. On success the last stdout line is the one JSON object

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

What the serve phase checks, and why it is ids and not logits: the
server emits token ids, so the greedy rows of the kernel run are compared
with the same requests served from a ``PADDLE_TPU_PALLAS=off`` artifact
and must be IDENTICAL ids on this seed. The two paths run the same op
chain on the same bf16 inputs and differ only in fp32 summation order, so
they agree unless two logits nearly tie; the greedy rows are kept short
to keep that exposure small, and ``SEED`` is a seed on which they agree.
A change that moves the numerics legitimately may have to move the seed —
say so in the PR.
"""

import argparse
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")          # listed in .gitignore
LOGS = os.path.join(HERE, "chiprun_out", "chip_smoke")
SEED = 20260926
MARK = "CHIP_SMOKE "          # prefix of a child's machine-readable lines

# (vocab, d_model, heads, layers, d_ff, slots, cache_len, chunk, buckets)
LM_FULL = dict(vocab=32000, d_model=512, n_heads=8, n_layers=6, d_ff=2048,
               slots=8, cache_len=2048, chunk=256, buckets=(64, 256),
               block_size=None,                  # the engine's default
               train_seq=2048, train_batch=8)
LM_TINY = dict(vocab=96, d_model=32, n_heads=2, n_layers=2, d_ff=64,
               slots=4, cache_len=64, chunk=16, buckets=(8, 16),
               block_size=8, train_seq=32, train_batch=2)


def say(*a):
    print(*a, flush=True)


def fail(msg: str):
    """End the run: non-zero exit, no result line."""
    print(f"chip_smoke: FAILED — {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


# ---------------------------------------------------------------------------
# parent side: run children one at a time
# ---------------------------------------------------------------------------

class Children:
    """Every process this script starts, so that every one is stopped."""

    def __init__(self):
        self.live = []

    def spawn(self, argv, *, env, stdin=None, stdout=None, stderr=None):
        p = subprocess.Popen(argv, env=env, cwd=HERE, stdin=stdin,
                             stdout=stdout, stderr=stderr, text=True,
                             start_new_session=True)
        self.live.append(p)
        return p

    def reap(self):
        for p in self.live:
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    pass
                p.wait()
        self.live = []


CHILDREN = Children()


def child_env(rehearsal: bool, **extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "") \
        if env.get("PYTHONPATH") else HERE
    env["PYTHONUNBUFFERED"] = "1"
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    env.update({k: str(v) for k, v in extra.items()})
    return env


def check_device(dev: dict, rehearsal: bool, who: str):
    if dev.get("platform") != "tpu" and not rehearsal:
        fail(f"{who} ran on {dev}: JAX found no TPU. This script is "
             f"evidence only on the chip (--rehearsal runs it on the CPU "
             f"to exercise the script itself, and proves nothing).")


def run_phase(name: str, rehearsal: bool, timeout: float, chips: int = 1,
              **env) -> dict:
    """Run ``chip_smoke.py --phase name`` as a child, wait for it, and
    return the records it printed (``MARK``-prefixed JSON lines merged
    into one dict). A non-zero exit, a timeout or a non-TPU device ends
    the run."""
    t0 = time.time()
    log_path = os.path.join(LOGS, f"{name}.log")
    argv = [sys.executable, os.path.abspath(__file__), "--phase", name,
            "--chips", str(chips)] + (["--rehearsal"] if rehearsal else [])
    with open(log_path, "w") as log:
        p = CHILDREN.spawn(argv, env=child_env(rehearsal, **env),
                           stdout=subprocess.PIPE, stderr=log)
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            CHILDREN.reap()
            fail(f"phase {name} exceeded {timeout:.0f}s (log: {log_path})")
    rec = {}
    for line in out.splitlines():
        if line.startswith(MARK):
            rec.update(json.loads(line[len(MARK):]))
        else:
            say(f"  [{name}] {line}")
    if "device" in rec:
        check_device(rec["device"], rehearsal, f"phase {name}")
    if p.returncode != 0 or "device" not in rec:
        with open(log_path) as f:
            tail = f.read()[-3000:]
        fail(f"phase {name} exited {p.returncode}\n{tail}")
    say(f"phase {name}: ok in {time.time() - t0:.1f}s on {rec['device']}")
    return rec


def make_requests(lm: dict, rng: random.Random):
    """>= 8 requests: mixed prompt lengths up to more than half the
    cache (so chunked prefill runs over context), two sharing a long
    prefix (so the prefix cache hits), greedy rows (compared between
    runs — short) and temperature/top-k rows (so the sampler samples)."""
    vocab, cache, chunk = lm["vocab"], lm["cache_len"], lm["chunk"]

    def toks(n):
        return [rng.randrange(vocab) for _ in range(n)]

    shared = toks(max(chunk, cache // 8) + 3)        # >= 256 at full size
    longest = cache // 2 + chunk // 2 + 5            # >= 1024 at full size
    reqs = [
        {"prompt": toks(5), "max_new": 8},
        {"prompt": shared + toks(7), "max_new": 8},
        {"prompt": toks(longest), "max_new": 8},
        {"prompt": toks(chunk + 11), "max_new": 6, "temperature": 0.8,
         "top_k": 40},
        {"prompt": shared + toks(19), "max_new": 8},
        {"prompt": toks(chunk // 2 + 1), "max_new": 12,
         "temperature": 1.0},
        {"prompt": toks(3 * chunk + 2), "max_new": 8},
        {"prompt": toks(chunk - 1), "max_new": 6, "temperature": 0.7,
         "top_k": 5},
        {"prompt": toks(17), "max_new": 8},
    ]
    for r in reqs:
        assert len(r["prompt"]) + r["max_new"] <= cache
    return reqs, len(shared)


def read_json_line(p, what: str, timeout: float, log_path: str) -> dict:
    """One JSON line off a serving child's stdout, bounded."""
    import threading
    box = []
    t = threading.Thread(target=lambda: box.append(p.stdout.readline()),
                         daemon=True)
    t.start()
    t.join(timeout)
    if not box or not box[0]:
        rc = p.poll()
        CHILDREN.reap()
        with open(log_path) as f:
            tail = f.read()[-3000:]
        fail(f"{what}: no line within {timeout:.0f}s "
             f"({'exited ' + str(rc) if rc is not None else 'still running'})"
             f"\n{tail}")
    return json.loads(box[0])


def drain(p, what: str, timeout: float):
    """SIGTERM, then exit code 0 once in-flight work has been answered
    (the drain contract)."""
    p.send_signal(signal.SIGTERM)
    try:
        rc = p.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        CHILDREN.reap()
        fail(f"{what} did not drain within {timeout:.0f}s of SIGTERM")
    if rc != 0:
        fail(f"{what} exited {rc} after SIGTERM (drain contract is exit 0)")


def placed(paths: dict) -> set:
    """Every path any kernel site of any compiled program placed."""
    return {v for rec in paths.values() for v in rec.values()}


def check_result(res: dict, req: dict, who: str):
    if res.get("error"):
        fail(f"{who}: request {res.get('id')} errored: {res['error']}")
    if len(res.get("tokens", ())) != req["max_new"]:
        fail(f"{who}: request {res.get('id')} returned "
             f"{len(res.get('tokens', ()))} tokens, wanted {req['max_new']}")
    if res.get("finish_reason") != "max_tokens":
        fail(f"{who}: request {res.get('id')} finish_reason "
             f"{res.get('finish_reason')!r}")
    for key in ("ttft_ms", "latency_ms"):
        if not isinstance(res.get(key), (int, float)) or res[key] <= 0:
            fail(f"{who}: request {res.get('id')} has no {key}: {res}")


def check_health(who: str, log_path: str, paths: dict) -> int:
    """The server's own health endpoint (announced on its stderr) must
    report the kernel paths of the ready line, and its metrics must show
    the shared-prefix requests hitting the prefix cache. Returns the
    hit-block count."""
    import urllib.request
    url = None
    with open(log_path) as f:
        for line in f:
            if "/healthz" in line:
                url = line.split("/metrics")[0].split()[-1]
    if url is None:
        fail(f"{who}: no health endpoint in {log_path}")
    doc = json.loads(urllib.request.urlopen(url + "/healthz",
                                            timeout=10).read())
    if doc.get("kernel_paths") != paths:
        fail(f"{who}: /healthz kernel_paths {doc.get('kernel_paths')} "
             f"!= ready line {paths}")
    metrics = urllib.request.urlopen(url + "/metrics",
                                     timeout=10).read().decode()
    hits = [float(line.split()[-1]) for line in metrics.splitlines()
            if line.startswith("engine_prefix_cache_hit_blocks_total")]
    if not hits or hits[0] <= 0:
        fail(f"{who}: the shared-prefix requests hit no prefix-cache block")
    return int(hits[0])


def serve_once(tag: str, model: str, reqs, rehearsal: bool, want_path: str,
               timeout: float) -> dict:
    """Start ``python -m paddle_tpu serve`` on stdio, read its ready
    line, send every request, check every result, SIGTERM, exit code 0
    (the drain contract). Returns ready doc + results by id."""
    t0 = time.time()
    log_path = os.path.join(LOGS, f"serve_{tag}.log")
    with open(log_path, "w") as log:
        p = CHILDREN.spawn(
            [sys.executable, "-m", "paddle_tpu", "serve",
             f"--model={model}", "--health_port=0"],
            env=child_env(rehearsal), stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=log)
        ready = read_json_line(p, f"serve[{tag}] ready line", timeout,
                               log_path)["replica_ready"]
        spawn_to_ready = time.time() - t0
        check_device(ready["device"], rehearsal, f"serve[{tag}]")
        paths = ready["kernel_paths"]
        sites = {s for rec in paths.values() for s in rec}
        if placed(paths) != {want_path}:
            fail(f"serve[{tag}]: expected every compiled program to "
                 f"place {want_path!r}, got {paths}")
        if sites != {"attention", "span_write", "sampler"} \
                or "decode" not in paths:
            fail(f"serve[{tag}]: kernel-path record incomplete: {paths}")
        for r in reqs:
            p.stdin.write(json.dumps(r) + "\n")
        p.stdin.flush()
        results = {}
        for _ in reqs:
            res = read_json_line(p, f"serve[{tag}] result", timeout,
                                 log_path)
            check_result(res, reqs[res["id"]], f"serve[{tag}]")
            results[res["id"]] = res
        if sorted(results) != list(range(len(reqs))):
            fail(f"serve[{tag}]: answered ids {sorted(results)}")
        ready["prefix_hit_blocks"] = check_health(
            f"serve[{tag}]", log_path, paths)
        drain(p, f"serve[{tag}]", 120)
    ttft = sorted(r["ttft_ms"] for r in results.values())
    say(f"serve[{tag}]: {len(results)} requests answered on "
        f"{ready['device']}; pallas={ready['pallas']} programs="
        f"{len(paths)} all {want_path}; ready after "
        f"{ready['time_to_ready_s']}s (spawn-to-ready "
        f"{spawn_to_ready:.1f}s); compile cache hits="
        f"{ready['compile_cache']['hits']} misses="
        f"{ready['compile_cache']['misses']}; prefix-cache hit blocks "
        f"{ready['prefix_hit_blocks']}; ttft ms min/max "
        f"{ttft[0]:.0f}/{ttft[-1]:.0f} (smoke timing, not a benchmark)")
    return {"ready": ready, "results": results}


def compare_greedy(a: dict, b: dict, reqs, what: str):
    rows = [i for i, r in enumerate(reqs) if not r.get("temperature")]
    bad = [i for i in rows
           if a["results"][i]["tokens"] != b["results"][i]["tokens"]]
    if bad:
        i = bad[0]
        fail(f"{what}: greedy rows {bad} differ, e.g. request {i}: "
             f"{a['results'][i]['tokens']} vs {b['results'][i]['tokens']}")
    say(f"{what}: {len(rows)} greedy rows identical ids")


def serve_phases(lm, rehearsal: bool, timeout: float):
    rng = random.Random(SEED)
    reqs, shared_len = make_requests(lm, rng)
    say(f"requests: {len(reqs)}, prompt lengths "
        f"{[len(r['prompt']) for r in reqs]}, shared prefix {shared_len}")
    want = "pallas_interpret" if rehearsal else "pallas"
    kernel = os.path.join(WORK, "lm_kernel.tar")
    cold = serve_once("cold", kernel, reqs, rehearsal, want, timeout)
    warm = serve_once("warm", kernel, reqs, rehearsal, want, timeout)
    off = serve_once("off", os.path.join(WORK, "lm_off.tar"), reqs,
                     rehearsal, "xla", timeout)
    compare_greedy(cold, warm, reqs, "kernel run vs its warm restart")
    compare_greedy(cold, off, reqs, "kernels vs PADDLE_TPU_PALLAS=off")
    c, w = cold["ready"], warm["ready"]
    say(f"warm start: time-to-ready {c['time_to_ready_s']}s -> "
        f"{w['time_to_ready_s']}s, cache hits "
        f"{c['compile_cache']['hits']} -> {w['compile_cache']['hits']} "
        f"(dir {w['compile_cache']['dir']})")
    if rehearsal:
        return      # interpreted kernels compile lazily: nothing to compare
    if w["compile_cache"]["hits"] <= 0:
        fail("second serve start reported no compile-cache hits")
    if c["compile_cache"]["misses"] == 0:
        say("first start found every program cached already (an earlier "
            "invocation filled the directory): no cold start to compare")
    elif w["time_to_ready_s"] >= c["time_to_ready_s"]:
        fail(f"warm time-to-ready {w['time_to_ready_s']}s is not shorter "
             f"than the cold {c['time_to_ready_s']}s")


def route_phase(lm, rehearsal: bool, chips: int, timeout: float):
    """``python -m paddle_tpu route --replicas N``: N replica processes
    on N distinct chips behind the router, whose own process never
    initialises a JAX backend (it could not share the chips if it did)."""
    rng = random.Random(SEED)
    reqs, _ = make_requests(lm, rng)
    reqs = reqs * 2          # enough in flight to reach every replica
    log_path = os.path.join(LOGS, "route.log")
    t0 = time.time()
    with open(log_path, "w") as log:
        p = CHILDREN.spawn(
            [sys.executable, "-m", "paddle_tpu", "route",
             f"--model={os.path.join(WORK, 'lm_kernel.tar')}",
             f"--replicas={chips}", "--max_in_flight=2"],
            env=child_env(rehearsal), stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=log)
        for r in reqs:
            p.stdin.write(json.dumps(r) + "\n")
        p.stdin.flush()
        results = []
        for _ in reqs:
            res = read_json_line(p, "route result", timeout, log_path)
            check_result(res, reqs[res["id"]], "route")
            results.append(res)
        drain(p, "route", 180)
    readies = {}
    with open(log_path) as f:
        for line in f:
            if line.startswith("route: replica_ready "):
                doc = json.loads(line[len("route: replica_ready "):])
                readies[doc["name"]] = doc
    if len(readies) != chips:
        fail(f"route: {len(readies)} replica ready lines, wanted {chips}")
    want = "pallas_interpret" if rehearsal else "pallas"
    for name, doc in sorted(readies.items()):
        check_device(doc["device"], rehearsal, f"route {name}")
        if placed(doc["kernel_paths"]) != {want}:
            fail(f"route {name}: kernel paths {doc['kernel_paths']}")
        say(f"route {name}: device {doc['device']} ready after "
            f"{doc['time_to_ready_s']}s")
    pins = {d["device"]["visible_chips"] for d in readies.values()}
    if len(pins) != chips:
        fail(f"route: replicas were pinned to chips {sorted(pins)}, "
             f"wanted {chips} distinct")
    served = {r["replica"] for r in results}
    if len(served) != chips:
        fail(f"route: only replicas {sorted(served)} answered, "
             f"wanted all {chips}")
    say(f"route: {len(results)} requests answered by {sorted(served)} on "
        f"chips {sorted(pins)} in {time.time() - t0:.1f}s; router parent "
        f"held no chip (all {chips} replicas got theirs)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on the CPU at a tiny size with interpreted "
                         "kernels: exercises this script, is not evidence")
    ap.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    lm = LM_TINY if args.rehearsal else LM_FULL
    if args.phase:
        return PHASES[args.phase](lm, args)
    if "jax" in sys.modules:
        fail("the parent imported jax")
    if args.rehearsal:
        say("REHEARSAL: CPU, tiny sizes, interpreted kernels — this run "
            "exercises chip_smoke.py and is NOT evidence about the chip")
    shutil.rmtree(WORK, ignore_errors=True)
    shutil.rmtree(LOGS, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(LOGS)
    t0 = time.time()
    # ready/results arrive in seconds once a server is up; the bound is
    # for a cold start that compiles every program first
    serve_timeout = 600.0
    try:
        exp = run_phase("export", args.rehearsal, 900)
        device = exp["device"]
        if args.chips == 1:
            if device["count"] != 1 and not args.rehearsal:
                fail(f"one-chip run found {device['count']} devices")
            serve_phases(lm, args.rehearsal, serve_timeout)
            rn = run_phase("train_resnet", args.rehearsal, 900)
            tl = run_phase("train_lm", args.rehearsal, 900)
            say(f"train: resnet50 cost {rn['costs'][0]:.4f} -> "
                f"{rn['costs'][-1]:.4f}, {rn['step_ms']:.1f} ms/step after "
                f"the compile step ({rn['compile_step_s']:.1f}s); flash LM "
                f"loss {tl['losses'][0]:.4f} -> {tl['losses'][-1]:.4f}, "
                f"{tl['step_ms']:.1f} ms/step after compile "
                f"({tl['compile_step_s']:.1f}s) — smoke timings, not "
                f"benchmark results")
        else:
            if device["count"] != args.chips and not args.rehearsal:
                fail(f"--chips {args.chips} run found {device['count']} "
                     f"devices")
            dp = run_phase("train_dp", args.rehearsal, 1200,
                           chips=args.chips)
            say(f"train_dp: cost {dp['costs'][0]:.4f} -> "
                f"{dp['costs'][-1]:.4f}; batch shards on devices "
                f"{dp['batch_devices']}; zero=1 optimizer-state shards on "
                f"{dp['opt_state_devices']}; bytes in use per device "
                f"{dp['bytes_in_use']}; {dp['step_ms']:.1f} ms/step "
                f"(smoke timing); collectives {dp['collectives']}")
            route_phase(lm, args.rehearsal, args.chips, serve_timeout)
    finally:
        CHILDREN.reap()
    say(f"chip_smoke: every phase passed in {time.time() - t0:.0f}s")
    if not os.environ.get("CHIP_SMOKE_KEEP"):
        shutil.rmtree(WORK, ignore_errors=True)
    result = {"ok": True, "device": {k: device[k] for k in
                                     ("platform", "kind", "count")}}
    if args.rehearsal:
        result = {"ok": True, "rehearsal": True, "evidence": False,
                  "device": result["device"]}
    print(json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# child side: each phase is one process that owns the chip while it runs
# ---------------------------------------------------------------------------

def emit(**rec):
    print(MARK + json.dumps(rec), flush=True)


def start_child(args):
    """Common child prologue: place the compile cache, touch JAX, report
    the device — and stop right here when it is not a TPU (the parent
    turns that into the run's failure)."""
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    import jax
    if args.rehearsal and args.chips > 1:
        jax.config.update("jax_num_cpu_devices", args.chips)
    dev = jax.devices()[0]
    emit(device={"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(jax.devices())})
    if dev.platform != "tpu" and not args.rehearsal:
        sys.exit(3)
    return jax


def lm_config(lm, **kw):
    import jax.numpy as jnp
    from paddle_tpu.models import transformer
    return transformer.TransformerConfig(
        vocab=lm["vocab"], d_model=lm["d_model"], n_heads=lm["n_heads"],
        n_layers=lm["n_layers"], d_ff=lm["d_ff"],
        max_len=max(lm["cache_len"], lm["train_seq"]),
        dtype=jnp.bfloat16, **kw)


def phase_export(lm, args):
    """Export the paged-engine artifact twice from one seeded model:
    kernels placed (the default policy on this platform; interpreted in
    the rehearsal) and ``PADDLE_TPU_PALLAS=off``."""
    jax = start_child(args)
    from paddle_tpu.io import lm_serving
    from paddle_tpu.models import transformer
    from paddle_tpu.serving.blocks import DEFAULT_BLOCK_SIZE
    cfg = lm_config(lm)
    params = transformer.init_params(jax.random.PRNGKey(SEED), cfg)
    block = lm["block_size"] or DEFAULT_BLOCK_SIZE
    check_cast_once(params, cfg, lm["slots"], block)
    for name, mode in (("lm_kernel.tar",
                        "interpret" if args.rehearsal else None),
                       ("lm_off.tar", "off")):
        if mode is None:
            os.environ.pop("PADDLE_TPU_PALLAS", None)
        else:
            os.environ["PADDLE_TPU_PALLAS"] = mode
        t0 = time.time()
        lm_serving.save_lm_artifact(
            os.path.join(WORK, name), params, cfg, batch=lm["slots"],
            prompt_len=8, cache_len=lm["cache_len"],
            engine_buckets=lm["buckets"], engine_block_size=block)
        print(f"exported {name} (block {block}, chunk {lm['chunk']}, "
              f"{lm['slots']} slots, cache {lm['cache_len']}) in "
              f"{time.time() - t0:.1f}s")
    return 0


def check_cast_once(params, cfg, slots: int, block: int):
    """On THIS device: a paged decode step over the tree an artifact
    stores (block matrices cast to the compute dtype once) gives the
    logits, bit for bit, of the step over the float32 tree, which casts
    them itself. Called in a child, after ``start_child``."""
    import jax
    import numpy as np
    from paddle_tpu.models import transformer
    cast = transformer.compute_dtype_params(params, cfg)
    if cast["blocks"]["qkv"].dtype != cfg.dtype:
        fail("compute_dtype_params left qkv in "
             f"{cast['blocks']['qkv'].dtype}")
    rng = np.random.RandomState(SEED)
    pool = transformer.init_block_pool(cfg, 2 * slots, block)
    pool = {n: jax.numpy.asarray(rng.standard_normal(t.shape), t.dtype)
            for n, t in pool.items()}
    pages = np.arange(2 * slots, dtype=np.int32).reshape(slots, 2)
    tok = rng.randint(0, cfg.vocab, slots).astype(np.int32)
    pos = rng.randint(0, 2 * block, slots).astype(np.int32)
    step = jax.jit(lambda p: transformer.decode_step_paged(
        p, pool, tok, pos, np.ones(slots, bool), pages, cfg,
        block_size=block, pallas="off")[0])
    a, b = np.asarray(step(params)), np.asarray(step(cast))
    if not np.array_equal(a, b):
        fail(f"decode logits over the cast-once tree differ from the "
             f"float32 tree's by up to {np.abs(a - b).max()}")
    print(f"cast once == cast every step: {a.shape} logits bit-equal")


def train_resnet(lm, args, mesh_chips: int = 0):
    """>= 5 steps of ResNet-50 (the recipe bench.py builds; a small
    CIFAR ResNet in the rehearsal) through ``paddle.trainer.SGD.train``
    on a synthetic reader. The reader repeats one batch, so a working
    optimizer must bring the cost down — at a tenth of bench.py's
    learning rate: 0.1 with momentum and no warm-up sends the first
    steps of a fresh ResNet-50 up (7.5, 3.0, 10.8, 24.5, ... on the
    repeated batch) before they come down, and six steps are not enough
    to see that."""
    jax = start_child(args)
    import numpy as np
    import paddle_tpu as paddle
    paddle.init(seed=SEED)
    if args.rehearsal:
        from paddle_tpu import layer
        from paddle_tpu.models import resnet
        dim, classes, batch, lr = 3 * 32 * 32, 10, 8, 0.05
        img = layer.data("image", paddle.data_type.dense_vector(dim))
        lbl = layer.data("label", paddle.data_type.integer_value(classes))
        cost = layer.classification_cost(
            resnet.resnet_cifar10(img, depth=8, class_num=classes), lbl,
            name="cost")
    else:
        import bench
        dim, classes, batch, lr = 3 * 224 * 224, 1000, 128, 0.01
        cost = bench.resnet50_cost(stem_s2d=True, fused_bn=False)
    steps = 6
    rng = np.random.RandomState(SEED % 2**31)
    samples = [(rng.rand(dim).astype(np.float32), int(rng.randint(classes)))
               for _ in range(batch)]

    def reader():
        for _ in range(steps):
            yield from samples

    dist = None
    if mesh_chips:
        from paddle_tpu import parallel
        from paddle_tpu.core import place
        mesh = place.make_mesh((mesh_chips,), (place.AXIS_DATA,))
        dist = parallel.data_parallel(mesh, zero=1)
    params = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params, parallel=dist,
        update_equation=paddle.optimizer.Momentum(momentum=0.9,
                                                  learning_rate=lr))
    costs, stamps = [], [time.perf_counter()]

    def handler(ev):
        if isinstance(ev, paddle.event.EndIteration):
            costs.append(float(ev.cost))      # a host read: the step ended
            stamps.append(time.perf_counter())
            print(f"step {ev.batch_id}: cost {costs[-1]:.4f} "
                  f"({stamps[-1] - stamps[-2]:.2f}s)")

    trainer.train(paddle.batch(reader, batch), num_passes=1,
                  event_handler=handler)
    if len(costs) != steps or not all(np.isfinite(costs)):
        raise AssertionError(f"costs {costs}")
    if not costs[-1] < costs[0]:
        raise AssertionError(f"cost did not fall: {costs}")
    dts = np.diff(stamps)
    emit(costs=costs, compile_step_s=float(dts[0]),
         step_ms=float(1000 * np.median(dts[1:])))
    return trainer, samples


def phase_train_resnet(lm, args):
    train_resnet(lm, args)
    return 0


def phase_train_dp(lm, args):
    """The same steps data-parallel over every chip with ZeRO-1: the
    batch and the optimizer state must have shards on ``--chips``
    distinct devices, each device must hold memory, and the compiled
    step's collectives are recorded (does zero=1 reduce-scatter here?)."""
    import re
    jax = start_child(args)
    n = len(jax.devices())
    if n != args.chips:
        raise AssertionError(f"{n} devices, wanted {args.chips}")
    trainer, samples = train_resnet(lm, args, mesh_chips=n)
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.parallel import spmd

    def devices_of(tree):
        return sorted({s.device.id for leaf in jax.tree_util.tree_leaves(tree)
                       for s in leaf.addressable_shards})

    feeds = trainer._feeder(None).feed(samples)
    feeds = jax.device_put(feeds, trainer.parallel.feed_shardings(feeds))
    batch_devices = devices_of(feeds)
    sharded_state = [leaf for leaf in
                     jax.tree_util.tree_leaves(trainer.opt_state)
                     if not leaf.sharding.is_fully_replicated]
    if not sharded_state:
        raise AssertionError("zero=1 sharded no optimizer-state leaf")
    state_devices = devices_of(sharded_state)
    for what, got in (("batch", batch_devices),
                      ("optimizer state", state_devices)):
        if len(got) != n:
            raise AssertionError(f"{what} shards sit on devices {got}, "
                                 f"wanted {n} distinct")
    in_use = {}
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        in_use[d.id] = int(stats.get("bytes_in_use", -1))
    if not args.rehearsal and min(in_use.values()) <= 0:
        raise AssertionError(f"a device holds nothing: {in_use}")
    step_args = (trainer.parameters.values, trainer.opt_state,
                 trainer.parameters.state, feeds,
                 jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0))
    hlo = trainer._plain_train_step.lower(*step_args).compile().as_text()
    ops = {}
    for op in re.findall(r"\s(all-reduce|reduce-scatter|all-gather|"
                         r"all-to-all|collective-permute)(?:-start)?\(",
                         hlo):
        ops[op] = ops.get(op, 0) + 1
    biggest = max(np.asarray(v).nbytes
                  for v in trainer.parameters.values.values())
    evidence = spmd.zero_collective_evidence(hlo, biggest)
    with open(os.path.join(LOGS, "train_dp_step.hlo.txt"), "w") as f:
        f.write(hlo)
    emit(batch_devices=batch_devices, opt_state_devices=state_devices,
         bytes_in_use=in_use,
         collectives={"ops": ops, "zero1_evidence": {
             k: evidence[k] for k in ("reduce_scatter", "param_all_gather",
                                      "full_grad_all_reduce")}})
    return 0


def phase_train_lm(lm, args):
    """3 steps of the served LM's train step with the Pallas flash
    kernels (forward and backward) at the bench sequence length."""
    jax = start_child(args)
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu import optimizer as popt
    from paddle_tpu.models import transformer
    if args.rehearsal:
        os.environ["PADDLE_TPU_PALLAS"] = "interpret"
    cfg = lm_config(lm, use_flash_attention=True)
    params = transformer.init_params(jax.random.PRNGKey(SEED), cfg)
    adam = popt.Adam(learning_rate=1e-3)
    opt_state = adam.tree_init_state(params)
    rng = np.random.RandomState(SEED % 2**31)
    tokens = jnp.asarray(rng.randint(
        0, cfg.vocab, (lm["train_batch"], lm["train_seq"])), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)

    def train_step(p, o, i):
        loss, g = jax.value_and_grad(transformer.lm_loss)(
            p, tokens, targets, cfg)
        p, o = adam.tree_update(i, g, p, o)
        return loss, p, o

    step = jax.jit(train_step, donate_argnums=(0, 1))
    losses, stamps = [], [time.perf_counter()]
    for i in range(3):
        loss, params, opt_state = step(params, opt_state,
                                       jnp.asarray(i, jnp.int32))
        losses.append(float(jax.block_until_ready(loss)))
        stamps.append(time.perf_counter())
        print(f"step {i}: loss {losses[-1]:.4f} "
              f"({stamps[-1] - stamps[-2]:.2f}s)")
    if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        raise AssertionError(f"losses {losses}")
    dts = np.diff(stamps)
    emit(losses=losses, compile_step_s=float(dts[0]),
         step_ms=float(1000 * np.median(dts[1:])))
    return 0


PHASES = {"export": phase_export, "train_resnet": phase_train_resnet,
          "train_dp": phase_train_dp, "train_lm": phase_train_lm}


if __name__ == "__main__":
    sys.exit(main())
