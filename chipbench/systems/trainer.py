"""Training cells: the v2 layer graph through ``paddle.trainer.SGD.train``
with a host reader, the path a training user runs.

One ``SGD.train`` call carries the whole run. Set-up builds the trainer
from the seed's weights, and its first steps (the compile among them)
go through the same call, reader and feeder as the window: the handler
takes the losses, the first gradient's norms (the momentum state after
one step IS the gradient) and the norms of the parameters' change after
the followed steps, then opens the window on that same object. Steps are
closed by the cost the trainer hands its event handler. When the window
has closed the reader ends, the trainer is freed, and the plain
reference follows the same first steps from the same weights and rows.
"""

import gc
import time

import numpy as np

from chipbench import compare, flops, harness, weights
from chipbench.generators import batches
from chipbench.references import resnet50 as ref


def cost_of(cfg: dict):
    """The v2 layer graph of the configuration's own sizes: depth,
    classes and image side are read from its file, whatever the cell."""
    import paddle_tpu as paddle
    from paddle_tpu import layer
    from paddle_tpu.models import resnet
    side = cfg["image_size"]
    img = layer.data("image", paddle.data_type.dense_vector(3 * side * side))
    lbl = layer.data("label", paddle.data_type.integer_value(cfg["classes"]))
    out = resnet.resnet_imagenet(
        img, depth=cfg["depth"], class_num=cfg["classes"], img_size=side,
        stem_space_to_depth=cfg["stem_space_to_depth"],
        fused_bn=cfg["fused_bn"])
    return layer.classification_cost(out, lbl, name="cost")


def build(cell, seed: int, trace: bool):
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    import paddle_tpu as paddle
    cfg = cell.config
    init = {"seed": int(seed) % (2 ** 31)}
    if trace:
        init["profile"] = True      # the trainer's scopes become annotations
    paddle.init(**init)
    from paddle_tpu.utils.flags import GLOBAL_FLAGS
    if str(GLOBAL_FLAGS.get("compute_dtype", "bfloat16")) != \
            cfg["compute_dtype"]:
        raise SystemExit(f"chipbench: the program computes in "
                         f"{GLOBAL_FLAGS.get('compute_dtype')}, the "
                         f"configuration states {cfg['compute_dtype']}")
    cost = cost_of(cfg)
    params = paddle.parameters.create(cost)
    # the reference's leaves follow the configuration's stage_blocks and
    # stage_widths, the program's its depth: the two have to agree
    shapes = ref.leaf_shapes(cfg["classes"], ref.stages_of(cfg))
    have = {k: tuple(v.shape) for k, v in params.values.items()}
    if have != shapes:
        odd = sorted(set(have.items()) ^ set(shapes.items()))[:6]
        raise SystemExit(f"chipbench: the program's leaves at depth "
                         f"{cfg['depth']} differ from the reference's at "
                         f"stage_blocks {cfg['stage_blocks']}: {odd}")
    params.values = dict(weights.resnet_weights(seed, shapes))
    opt = cfg["optimizer"]
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(
            momentum=opt["momentum"], learning_rate=opt["learning_rate"]))
    return paddle, trainer, shapes


def stat_snapshot(names):
    from paddle_tpu.utils.stat import global_stats
    return {n: (global_stats.get(n).total_s, global_stats.get(n).count)
            for n in names}


SCOPES = ("feed", "feed/convert", "feed/transfer", "train_step/dispatch",
          "host_sync")


def drive(cell, paddle, trainer, shapes, seed, seconds, tracer=None,
          break_step=None, break_feed=None):
    """The one ``SGD.train`` call: followed steps, warm steps, window.
    ``break_step(trainer, n)`` and ``break_feed="half"`` are the tests'
    hooks: they break the timed path underneath the run (a step whose
    state does not move; half of each batch left out)."""
    cfg, tr = cell.config, cell.traffic
    B, side = cfg["batch_size"], cfg["image_size"]
    rows, labels = batches.pool(tr, seed, B, 3 * side * side, cfg["classes"])
    follow, warm = int(tr["follow_steps"]), int(tr["warm_steps"])
    w0 = weights.resnet_weights(seed, shapes)
    st = {"stop": False, "n": 0, "losses": [], "t_open": None,
          "t_close": None, "steps": 0, "trace_on": False, "ends": [],
          "scope_s": []}
    trace_s = float(tr.get("trace_s", 3))

    fed = B // 2 if break_feed == "half" else B

    def reader():
        k = 0
        while not st["stop"]:
            r, l = batches.batch_of(rows, labels, B, k)
            k += 1
            for i in range(fed):
                yield (r[i], int(l[i]))

    from paddle_tpu.utils.stat import global_stats

    def handler(ev):
        if not isinstance(ev, paddle.event.EndIteration):
            return
        now = time.perf_counter()
        st["n"] += 1
        n = st["n"]
        if break_step is not None:
            break_step(trainer, n)
        if n <= follow:
            st["losses"].append(float(ev.cost))
        if n == 1:
            # momentum from zero: the state after one step IS the gradient
            st["grad_norm"] = ref.leaf_norms(trainer.opt_state)
            st["grad_weights"] = {
                k: np.asarray(v)
                for k, v in ref.weight_leaves(trainer.opt_state).items()}
        if n == follow:
            st["change_norm"] = ref.leaf_norms_of_change(
                trainer.parameters.values, st.pop("w0"))
        if n == max(warm, follow):
            st["t_open"], st["open_wall"] = time.perf_counter(), time.time()
            st["stats_open"] = stat_snapshot(SCOPES)
            from paddle_tpu.utils import compile_cache
            st["compile_cache"] = compile_cache.stats()
            if tracer is not None:
                tracer.start()
                st["trace_on"] = True
                st["t_open"] = time.perf_counter()
            st["ends"].append(st["t_open"])
            st["scope_s"].append([st["stats_open"][n][0] for n in SCOPES])
            return
        if st["t_open"] is None or st["t_close"] is not None:
            return
        st["steps"] += 1
        st["ends"].append(now)
        st["scope_s"].append([global_stats.get(n).total_s for n in SCOPES])
        if st["trace_on"] and now - st["t_open"] >= trace_s:
            tracer.stop()
            st["trace_on"] = False
            st["traced_steps"] = st["steps"]
        if now - st["t_open"] >= seconds:
            st["t_close"] = now
            st["stats_close"] = stat_snapshot(SCOPES)
            st["stop"] = True

    st["w0"] = w0
    trainer.train(paddle.batch(reader, fed), num_passes=1,
                  event_handler=handler)
    if st["trace_on"]:
        tracer.stop()
        st["traced_steps"] = st["steps"]
    if st["t_close"] is None:
        raise RuntimeError("the window never closed")
    st["first_batches"] = [batches.batch_of(rows, labels, B, k)
                           for k in range(follow)]
    return st


def reference_readings(cell, seed, shapes, first_batches, fmt="f32",
                       drop_half=False):
    opt = cell.config["optimizer"]
    w0 = weights.resnet_weights(seed, shapes)
    return ref.first_steps(w0, first_batches, opt["learning_rate"],
                           opt["momentum"], fmt=fmt, drop_half=drop_half,
                           stages=ref.stages_of(cell.config))


def readings_gap(prog: dict, refr: dict) -> dict:
    """The numbers read: each followed step's loss; the first gradient's
    norm and the change's norm, by the worst leaf and by the median leaf;
    and the first gradient's weight matrices as one vector, the norm of
    program less reference over the reference's. Leaves whose reference
    gradient is under a thousandth of the median leaf's are left out of
    the change. ``chipbench/limits`` says which of these are compared."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], refr["losses"])):
        out[f"loss_{i + 1}_gap"] = abs(a - b) / abs(b)
    out["grad_norm_gap"], out["grad_norm_leaf"] = compare.worst_leaf_gap(
        prog["grad_norm"], refr["grad_norm"])
    med = float(np.median(list(refr["grad_norm"].values())))
    skip = [k for k, g in refr["grad_norm"].items() if g < 1e-3 * med]
    out["change_norm_gap"], out["change_norm_leaf"] = compare.worst_leaf_gap(
        prog["change_norm"], refr["change_norm"], skip=skip)
    out["grad_norm_gap_median"] = compare.median_leaf_gap(
        prog["grad_norm"], refr["grad_norm"])
    out["change_norm_gap_median"] = compare.median_leaf_gap(
        prog["change_norm"], refr["change_norm"], skip=skip)
    out["grad_diff_rel"] = ref.diff_rel(prog["grad_weights"],
                                        refr["grad_weights"])
    for name, prefix in (("fc", "res_fc"), ("stage5", "res5_"),
                         ("stage4", "res4_"), ("stage2", "res2_")):
        out[f"grad_diff_rel_{name}"] = ref.diff_rel(
            prog["grad_weights"], refr["grad_weights"], prefix)
    return out


def step_spread(ends, scope_s) -> dict:
    """How the window's steps spread (a note, no metric), whole and by the
    trainer's scopes, in ms: a stall shows as a few steps far over the
    median, a slow host as every quantile higher, a loop of period two as
    ``even_mean`` apart from ``odd_mean``."""
    def spread(ms):
        o = np.sort(ms)
        q = lambda p: float(o[min(len(o) - 1, int(p * len(o)))])
        return {"p10": q(0.1), "p50": q(0.5), "p90": q(0.9),
                "max": float(o[-1]), "even_mean": float(ms[0::2].mean()),
                "odd_mean": float(ms[1::2].mean())}
    out = {"step": spread(1000.0 * np.diff(np.asarray(ends)))}
    per = 1000.0 * np.diff(np.asarray(scope_s), axis=0)
    for i, name in enumerate(SCOPES):
        if per[:, i].any():
            out[name] = spread(per[:, i])
    return out


def run(cell, *, seed, seconds, trace, device, t_start, keep_trace=False,
        work=None, break_step=None, break_feed=None) -> int:
    paddle, trainer, shapes = build(cell, seed, trace)
    tracer = harness.TraceWindow(keep=keep_trace) if trace else None
    st = drive(cell, paddle, trainer, shapes, seed, seconds, tracer,
               break_step=break_step, break_feed=break_feed)
    window_s = st["t_close"] - st["t_open"]
    e2e = {"train_step_ms": 1000.0 * window_s / st["steps"],
           "setup_s": st["open_wall"] - t_start}
    mem = harness.memory_peak_bytes(cell.chips)
    B = cell.config["batch_size"]
    step_flops = flops.resnet_train_flops(
        B, cell.config["image_size"], cell.config["classes"],
        cell.config["stage_blocks"], cell.config["stage_widths"])
    ctx = None
    if trace:
        ctx = {"cell": cell, "spans": {"compile_cache": st["compile_cache"]},
               "stats": {n: (st["stats_close"][n][0] - st["stats_open"][n][0],
                             st["stats_close"][n][1] - st["stats_open"][n][1])
                         for n in SCOPES},
               "steps": st["steps"], "window_s": window_s,
               "step_flops": step_flops,
               "peaks": harness.peaks_for(device["kind"]),
               "trace": tracer.read(cell.chips), "chips": cell.chips}
    prog = {"losses": st["losses"], "grad_norm": st["grad_norm"],
            "change_norm": st["change_norm"],
            "grad_weights": st["grad_weights"]}
    first_batches = st.pop("first_batches")
    trainer.parameters.values = trainer.opt_state = None
    trainer.parameters.state = None
    del trainer
    gc.collect()
    refr = reference_readings(cell, seed, shapes, first_batches)
    gaps = readings_gap(prog, refr)
    del refr["grad_weights"], prog["grad_weights"]
    compared = compare.judge(gaps, cell.limits)
    correct = all(c["ok"] for c in compared.values()) and bool(compared)
    dev = dict(device, memory_peak_bytes=mem)
    per_layer, breakdown = {}, None
    if trace:
        dev["busy_s"] = ctx["trace"]["busy_s"]
        dev["window_s"] = ctx["trace"]["window_s"]
        per_layer = harness.read_per_layer(cell, ctx)
        breakdown = ctx["trace"]["breakdown"]
    notes = {"steps": st["steps"], "window_s": window_s,
             "losses": prog["losses"], "reference_losses": refr["losses"],
             "worst_leaves": {"grad": gaps["grad_norm_leaf"],
                              "change": gaps["change_norm_leaf"]},
             "images_per_s": B * st["steps"] / window_s,
             "step_ms": step_spread(st["ends"], st["scope_s"]),
             "readings": {k: v for k, v in gaps.items()
                          if not isinstance(v, str)}}
    harness.finish(cell, trace=trace, correct=correct,
                   attempted=st["steps"], failed=0, end_to_end=e2e,
                   per_layer=per_layer, device=dev, compared=compared,
                   breakdown=breakdown, notes=notes)
    return 0
