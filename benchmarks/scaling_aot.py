"""Multi-chip scaling evidence via AOT compilation for a real TPU topology.

Where no multi-chip host is at hand, a multi-process CPU-mesh throughput
curve would measure core contention, not scaling. What IS available
everywhere is the real TPU compiler: `jax.experimental.topologies`
describes a v5e pod slice and `jit(...).lower().compile()` runs the full
XLA:TPU pipeline — SPMD partitioning, collective insertion, and the
latency-hiding scheduler — exactly as it would for 8 physical chips.

This tool AOT-compiles a DP train step (the flagship ResNet-50 via the
bench.py builder contract, or ``--model mlp`` — a three-layer Adam MLP
that compiles in seconds, for iterating on collective patterns) over a
v5e mesh and extracts from the optimized, SCHEDULED HLO:

  1. every async collective pair (`all-reduce-start` → `all-reduce-done`)
     with its tensor bytes;
  2. how much convolution/fusion work the scheduler placed INSIDE each
     start→done window — the direct evidence that gradient all-reduces
     overlap the backward;
  3. an analytic step-time model: hidden collectives cost max(0,
     t_comm − t_overlapped_compute); with the measured single-chip step
     time this yields the DP scaling efficiency the north star asks for;
  4. with ``--num-slices N``: which collectives cross the slice (DCN)
     boundary and at what size — under ``--zero2``/``--zero3`` the
     hierarchical contract is that ONLY 1/N-sharded gradient tensors
     cross DCN (ICI reduce-scatter inside the slice first), reported as
     ``hierarchical_ok`` / ``largest_dcn_collective_bytes``.

Reference protocol being matched: the 4-GPU speedup tables in
/root/reference/benchmark/README.md:72-93 (their evidence was measured
wall-clock; ours is the compiler's actual schedule + measured single-chip
step time, the feasible substitute in a 1-chip environment).

Usage:  python benchmarks/scaling_aot.py [--topology v5e:2x4]
            [--batch-per-chip 128] [--zero 0..3 | --zero1/--zero2/--zero3]
            [--model resnet50|mlp] [--num-slices N]
"""

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np


def build_step(batch_per_chip, n_chips, mesh, batch_axes=("data",),
               zero_stage=0):
    """``zero_stage>=1`` applies the ZeRO weight-update sharding
    (parallel/spmd.py): optimizer state + update shard over the ``data``
    axis, so the TPU pipeline forms reduce-scatter + post-update
    all-gather instead of the full-gradient all-reduce; stage 3 stores
    the params as 1/N shards with on-use all-gathers. Returns
    (jitted_fn, abstract_args, largest_param_bytes)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import layer
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel import spmd as pspmd
    from paddle_tpu.topology import Topology, Value
    from paddle_tpu.utils.rng import KeySource
    from jax.sharding import NamedSharding, PartitionSpec as P

    img = layer.data("image", paddle.data_type.dense_vector(3 * 224 * 224))
    lbl = layer.data("label", paddle.data_type.integer_value(1000))
    out = resnet.resnet_imagenet(img, depth=50, class_num=1000,
                                 stem_space_to_depth=True)
    cost = layer.classification_cost(out, lbl, name="cost")
    topo = Topology(cost)
    opt = paddle.optimizer.Momentum(momentum=0.9, learning_rate=0.1)
    opt.bind(topo.param_specs())

    # abstract init: eval_shape traces the initializers without executing,
    # so no backend is touched until the AOT compile itself
    def _make():
        params = paddle.parameters.create(cost, KeySource(42))
        return params.values, params.state, opt.init_state(params.values)

    values_sds, state_sds, opt_sds = jax.eval_shape(_make)
    fwd = topo.compile()
    dist = (pspmd.DistConfig(mesh, zero_stage=zero_stage)
            if zero_stage >= 1 else None)
    comp_sh = dist.param_shardings(values_sds) if dist is not None else None

    def train_step(p, o, s, images, labels, step):
        if dist is not None and dist.zero_stage >= 3:
            p = jax.lax.with_sharding_constraint(p, comp_sh)

        def loss_fn(p):
            outs, ns = fwd(p, s, {"image": Value(images),
                                  "label": Value(labels)}, is_training=True)
            return jnp.mean(outs["cost"].array.astype(jnp.float32)), ns

        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        if dist is not None:
            np_, no_ = pspmd.zero_constrained_update(dist, opt, step,
                                                     grads, p, o)
        else:
            np_, no_ = opt.update(step, grads, p, o)
        return loss, np_, no_, ns

    rep = NamedSharding(mesh, P())
    dat = NamedSharding(mesh, P(batch_axes))
    gb = batch_per_chip * n_chips
    abstract = (values_sds, opt_sds, state_sds,
                jax.ShapeDtypeStruct((gb, 224, 224, 3), jnp.float32),
                jax.ShapeDtypeStruct((gb,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
    if dist is not None:
        opt_sharding = dist.state_shardings(opt_sds)
        param_sharding = dist.store_shardings(values_sds)
    else:
        opt_sharding = jax.tree.map(lambda _: rep, abstract[1])
        param_sharding = jax.tree.map(lambda _: rep, abstract[0])
    shardings = (param_sharding, opt_sharding,
                 jax.tree.map(lambda _: rep, abstract[2]), dat, dat, rep)
    jf = jax.jit(train_step, in_shardings=shardings,
                 out_shardings=(rep, shardings[0], shardings[1],
                                shardings[2]))
    sizes = [int(np.prod(v.shape)) * v.dtype.itemsize
             for v in jax.tree_util.tree_leaves(values_sds)]
    return jf, abstract, {"largest": max(sizes), "total": sum(sizes)}


def build_step_mlp(batch_per_chip, n_chips, mesh, batch_axes=("data",),
                   zero_stage=0, dim=1024, hidden=4096):
    """A three-layer Adam MLP train step — big enough that its param
    collectives dominate scalar bookkeeping, small enough that the
    deviceless XLA:TPU compile takes seconds (the ResNet-50 path takes
    ~20 min on this one-core host), for iterating on the ZeRO collective
    patterns and the multi-slice DCN analysis."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.parallel import spmd as pspmd
    from jax.sharding import NamedSharding, PartitionSpec as P

    opt = paddle.optimizer.Adam(learning_rate=0.02)
    params = {"w1": jax.ShapeDtypeStruct((dim, hidden), jnp.float32),
              "b1": jax.ShapeDtypeStruct((hidden,), jnp.float32),
              "w2": jax.ShapeDtypeStruct((hidden, hidden), jnp.float32),
              "b2": jax.ShapeDtypeStruct((hidden,), jnp.float32),
              "w3": jax.ShapeDtypeStruct((hidden, dim), jnp.float32)}
    opt_state = {k: (v, v) for k, v in params.items()}   # Adam (m, v)
    dist = (pspmd.DistConfig(mesh, zero_stage=zero_stage)
            if zero_stage >= 1 else None)
    rep = NamedSharding(mesh, P())
    dat = NamedSharding(mesh, P(batch_axes))
    if dist is not None:
        store = dist.store_shardings(params)
        comp = dist.param_shardings(params)
        upd = dist.zero_update_shardings(params)
        st = dist.state_shardings(opt_state)
    else:
        store = {k: rep for k in params}
        st = {k: (rep, rep) for k in params}

    def train_step(p, o, x, y, step):
        if dist is not None and dist.zero_stage >= 3:
            p = jax.lax.with_sharding_constraint(p, comp)

        def loss_fn(p):
            h = jnp.maximum(x @ p["w1"] + p["b1"], 0.0)
            h = jnp.maximum(h @ p["w2"] + p["b2"], 0.0)
            return jnp.mean((h @ p["w3"] - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        if dist is not None:
            np_, no_ = pspmd.zero_constrained_update(
                dist, opt, step, grads, p, o, update_shardings=upd,
                keep_shardings=store, state_shardings=st)
        else:
            np_, no_ = opt.update(step, grads, p, o)
        return loss, np_, no_

    gb = batch_per_chip * n_chips
    abstract = (params, opt_state,
                jax.ShapeDtypeStruct((gb, dim), jnp.float32),
                jax.ShapeDtypeStruct((gb, dim), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32))
    jf = jax.jit(train_step, in_shardings=(store, st, dat, dat, rep),
                 out_shardings=(rep, store, st))
    sizes = [int(np.prod(v.shape)) * 4
             for v in jax.tree_util.tree_leaves(params)]
    return jf, abstract, {"largest": max(sizes), "total": sum(sizes)}


_SIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
         "u8": 1, "pred": 1, "f64": 8}


def _shape_bytes(sig: str) -> int:
    """Bytes of one HLO shape string like 'f32[256,128]{1,0}' or a tuple."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", sig):
        dt, dims = m.group(1), m.group(2)
        if dt not in _SIZE:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _SIZE[dt]
    return total


def analyze_schedule(txt: str):
    """Parse the scheduled entry computation.

    Two evidence modes, depending on what the XLA build emits:
    - async ``*-start``/``*-done`` pairs (all-reduce, all-gather,
      reduce-scatter) → per-window overlap (compute ops scheduled inside
      each window);
    - sync collectives in a scheduled module (this build) → PLACEMENT
      evidence: a gradient collective interleaved mid-backward (compute
      scheduled after it) is what lets the runtime overlap it; a clump
      at the end of the schedule cannot overlap anything.

    Shape parsing is layout-robust: TPU shapes carry tile annotations
    with parens (``{3,2,1,0:T(8,128)(2,1)}``), so the op line is split
    at the opcode token instead of regex-matching the signature."""
    from paddle_tpu.parallel.spmd import FUSED_REDUCE_SCATTER_RE

    entry = txt[txt.index("ENTRY"):]
    lines = entry.splitlines()
    events = []       # (idx, kind, name, bytes, op)
    start_of = {}
    compute_lines = []
    op_re = re.compile(
        r"\s*%([\w.\-]+)\s*=\s*(.*?)\b"
        r"(all-reduce-start|all-reduce-done|all-reduce|"
        r"all-gather-start|all-gather-done|all-gather|"
        r"reduce-scatter-start|reduce-scatter-done|reduce-scatter|"
        r"fusion|convolution|custom-call)\(")
    megascale_send_bytes = 0
    megascale_sends = 0
    megascale_send_max = 0
    for i, ln in enumerate(lines):
        # multi-slice modules express the cross-slice (DCN) phase of the
        # hierarchical all-reduce as megascale-annotated send/recv host
        # transfers, not HLO collectives — count the send payloads
        if "megascale_transfer_type" in ln and re.match(r"\s*%send", ln):
            sig_m = re.match(r"\s*%[\w.\-]+ = (.*?)\bsend\(", ln)
            if sig_m:
                b = _shape_bytes(sig_m.group(1))
                megascale_send_bytes += b
                megascale_send_max = max(megascale_send_max, b)
                megascale_sends += 1
        # XLA:TPU lowers reduce-scatter to a kCustom fusion calling an
        # %all-reduce-scatter computation (the --zero grad sync): count
        # the call site as the collective it is (matcher shared with
        # paddle_tpu.parallel.spmd.zero_collective_evidence)
        if FUSED_REDUCE_SCATTER_RE.search(ln):
            sig_m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?)\bfusion\(",
                             ln)
            if sig_m:
                events.append((i, "reduce-scatter", f"fused_rs.{i}",
                               _shape_bytes(sig_m.group(1)),
                               "reduce-scatter"))
            continue
        m = op_re.match(ln)
        if not m:
            continue
        name, sig, kind = m.group(1), m.group(2), m.group(3)
        if kind.endswith("-start"):
            op = kind[:-len("-start")]
            # async start's shape is the (operand, result) tuple — the
            # wire traffic of an all-reduce is ONE copy of the gradient,
            # not both halves; gathers/scatters carry the bigger half
            b = _shape_bytes(sig)
            events.append((i, "start", name,
                           b // 2 if op == "all-reduce" else b, op))
            # the start line carries the replica_groups: keep them so
            # the DCN classifier sees async collectives too (a slice-
            # spanning async gather must not escape hierarchical_ok)
            start_of[name] = (i, _parse_group(lines[i]))
        elif kind.endswith("-done"):
            dep = re.search(kind + r"\(.*?%?([\w.\-]+)\)", ln)
            # the done's own shape is the collective RESULT (shard for
            # reduce-scatter, full tensor for all-gather/all-reduce)
            events.append((i, "done", dep.group(1) if dep else name,
                           _shape_bytes(sig), kind[:-len("-done")]))
        elif kind in ("all-reduce", "reduce-scatter", "all-gather"):
            events.append((i, kind, name, _shape_bytes(sig), kind))
        else:
            compute_lines.append((i, kind, ln))
    windows = []
    for i, k, name, nbytes, op in events:
        if k == "done":
            entry_s = start_of.get(name)
            if entry_s is not None:
                s, group = entry_s
                sbytes, sop = next(
                    (b, o) for (j, kk, n2, b, o) in events
                    if j == s and kk == "start")
                inside = [c for c in compute_lines if s < c[0] < i]
                # the done op's result shape is the true collective
                # result (shard for reduce-scatter, full for gather) —
                # the start tuple bundles operand+result, which would
                # feed the (g-1)x reduce-scatter wire factor ~g-fold
                # too many bytes
                windows.append({"start_line": s, "done_line": i,
                                "bytes": nbytes if nbytes else sbytes,
                                "op": sop,
                                "group_size": len(group) if group
                                else None,
                                "group_example": group[:16] if group
                                else None,
                                "group_min": min(group) if group
                                else None,
                                "group_max": max(group) if group
                                else None,
                                "compute_ops_inside": len(inside),
                                "conv_ops_inside": sum(
                                    1 for c in inside
                                    if c[1] == "convolution")})
    # placement analysis for sync collectives in the scheduled stream
    comp_idx = [i for (i, _, _) in compute_lines]
    n_lines = max(1, len(lines))
    sync = []
    unparsed = []
    for (i, k, name, b, op) in events:
        if k not in ("all-reduce", "reduce-scatter", "all-gather"):
            continue
        after = sum(1 for j in comp_idx if j > i)
        group = _parse_group(lines[i])
        # a replica_groups encoding _parse_group doesn't know falls back
        # to all-devices-over-ICI in the wire model — FLAG it so a
        # misparse is visible in the artifact instead of silently
        # misclassifying DCN-crossing collectives (ADVICE.md round-5)
        group_unparsed = (group is None
                          and "replica_groups=" in lines[i])
        if group_unparsed:
            unparsed.append({"name": name, "op": k,
                             "line": lines[i].strip()[:300]})
        sync.append({"name": name, "op": k, "bytes": b,
                     "pos_frac": round(i / n_lines, 4),
                     "compute_ops_after": after,
                     "group_size": len(group) if group else None,
                     "group_example": group[:16] if group else None,
                     "group_min": min(group) if group else None,
                     "group_max": max(group) if group else None,
                     "group_unparsed": group_unparsed})
    return {"async_windows": windows, "sync_all_reduces": sync,
            "total_compute_ops": len(compute_lines),
            "unparsed_replica_groups": unparsed,
            "megascale_sends": megascale_sends,
            "megascale_send_bytes": megascale_send_bytes,
            "megascale_send_max_bytes": megascale_send_max}


def _parse_topology_devices(name):
    """Per-slice device count from an `AxB`-style topology name
    ('v5e:2x4' → 8, 'v4:2x2x2' → 8, 'v5e:8' → 8); None when the name
    carries no parseable dims (use --num-devices then)."""
    m = re.search(r"(\d+(?:x\d+)+)", name)
    if m:
        n = 1
        for d in m.group(1).split("x"):
            n *= int(d)
        return n
    m = re.search(r":(\d+)$", name)
    return int(m.group(1)) if m else None


def _parse_group(ln):
    """First replica group of a collective line as a device-id list.
    Two HLO formats: iota `replica_groups=[G,S]<=[N]` (G groups of S,
    group 0 = 0..S-1 in iota order) and explicit
    `replica_groups={{0,8},{1,9},...}`. Unknown encodings return None —
    the caller flags them in the artifact (`group_unparsed`) rather
    than trusting the all-devices default silently."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                  r"(T\([\d,]+\))?", ln)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        if m.group(4):
            # transposed iota: group 0's members stride by G
            return [i * g for i in range(s)]
        return list(range(s))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", ln)
    if m:
        return [int(d) for d in m.group(1).split(",")]
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="v5e:2x4")
    ap.add_argument("--batch-per-chip", type=int, default=128)
    ap.add_argument("--model", choices=("resnet50", "mlp"),
                    default="resnet50",
                    help="resnet50: the flagship bench step (~20 min "
                    "deviceless compile on one core); mlp: three-layer "
                    "Adam MLP, compiles in seconds — for ZeRO collective "
                    "/ multi-slice DCN analysis")
    ap.add_argument("--mlp-dim", type=int, default=1024)
    ap.add_argument("--mlp-hidden", type=int, default=4096)
    ap.add_argument("--single-chip-ms", type=float, default=50.3,
                    help="measured single-chip step ms at this per-chip "
                    "batch (BENCHMARKS.md resnet50 bs=128: 52.59 unfused, "
                    "50.3 = 2543.6 img/s best fused-off config)")
    ap.add_argument("--ici-gbps", type=float, default=45.0,
                    help="per-link ICI bandwidth GB/s each direction "
                    "(v5e: 45 GB/s per link)")
    ap.add_argument("--dcn-gbps", type=float, default=12.5,
                    help="per-host DCN bandwidth GB/s (conservative "
                    "100 Gbps NIC default) for slice-crossing groups")
    ap.add_argument("--out", default=None)
    ap.add_argument("--num-slices", type=int, default=1,
                    help="multi-slice pod: DP spans a hybrid dcn x data "
                    "mesh; the gradient all-reduce crosses DCN")
    ap.add_argument("--hlo-file", default=None,
                    help="analyze a previously dumped scheduled-HLO text "
                    "instead of recompiling (the deviceless XLA:TPU "
                    "compile of the resnet50 step takes ~20 min on one "
                    "core)")
    ap.add_argument("--num-devices", type=int, default=None,
                    help="per-slice device count for --hlo-file analysis "
                    "when the topology name has no AxB dims to parse")
    ap.add_argument("--dump-hlo", default=None,
                    help="save the compiled HLO text here for --hlo-file "
                    "reuse")
    ap.add_argument("--zero", type=int, default=0, choices=(0, 1, 2, 3),
                    help="ZeRO stage: 1 shards opt state + update over "
                    "the data axis (schedule shows reduce-scatter + "
                    "post-update all-gather instead of the full-grad "
                    "all-reduce); 2 shards the gradients; 3 stores "
                    "params sharded with on-use all-gathers "
                    "(docs/howto_distributed.md)")
    ap.add_argument("--zero1", dest="zero", action="store_const",
                    const=1, help="alias for --zero 1")
    ap.add_argument("--zero2", dest="zero", action="store_const",
                    const=2, help="alias for --zero 2")
    ap.add_argument("--zero3", dest="zero", action="store_const",
                    const=3, help="alias for --zero 3")
    args = ap.parse_args()

    param_info = None
    if args.hlo_file:
        n = args.num_devices or _parse_topology_devices(args.topology)
        if not n:
            ap.error(f"cannot derive a device count from topology "
                     f"{args.topology!r}; pass --num-devices")
        n *= args.num_slices
        with open(args.hlo_file) as f:
            txt = f.read()
        print(f"analyzing saved HLO {args.hlo_file} "
              f"({args.topology}, {n} devices)")
    else:
        import jax
        from jax.experimental import topologies
        from jax.sharding import Mesh

        kw = {"num_slices": args.num_slices} if args.num_slices > 1 else {}
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=args.topology,
                                            **kw)
        n = len(topo.devices)
        if args.num_slices > 1:
            # hybrid mesh: slice-crossing axis (DCN) outermost, ICI DP
            # inner — the distributed.hybrid_mesh layout; the batch
            # shards over BOTH axes (pure DP across the pod) while the
            # ZeRO shard axis stays the inner ICI axis (hierarchical)
            mesh = Mesh(np.array(topo.devices).reshape(
                args.num_slices, n // args.num_slices), ("dcn", "data"))
            batch_axes = ("dcn", "data")
        else:
            mesh = Mesh(np.array(topo.devices).reshape(n), ("data",))
            batch_axes = ("data",)
        print(f"topology {args.topology} x{args.num_slices} slices: {n} "
              f"devices; {args.model} DP train step, per-chip batch "
              f"{args.batch_per_chip}, zero={args.zero}")

        builder = (build_step if args.model == "resnet50"
                   else lambda *a, **kw2: build_step_mlp(
                       *a, dim=args.mlp_dim, hidden=args.mlp_hidden,
                       **kw2))
        jf, abstract, param_info = builder(
            args.batch_per_chip, n, mesh, batch_axes=batch_axes,
            zero_stage=args.zero)
        lowered = jf.lower(*abstract)
        compiled = lowered.compile()
        txt = compiled.as_text()
        if args.dump_hlo:
            with open(args.dump_hlo, "w") as f:
                f.write(txt)
    sched = analyze_schedule(txt)

    n_async = len(sched["async_windows"])
    overlapped = [w for w in sched["async_windows"]
                  if w["compute_ops_inside"] > 0]
    ops_inside = sum(w["compute_ops_inside"] for w in sched["async_windows"])
    n_per_slice = n // max(1, args.num_slices)

    _WIRE_FACTOR = {
        "all-reduce": lambda g: 2 * (g - 1) / g,
        "all-gather": lambda g: (g - 1) / g,
        "reduce-scatter": lambda g: float(g - 1),
    }

    def crosses_dcn(c):
        """Whether this collective's replica group spans slices —
        decided from the group's min/max member ids, which is EXACT:
        any member outside the min's slice would displace either the
        min or the max into a different slice (the truncated
        group_example preview is display-only; a 32-wide group's first
        16 members can all sit inside slice 0). A collective with NO
        parseable group (the fused reduce-scatter call site carries its
        groups inside the called computation) is intra-slice:
        multi-slice TPU builds express the cross-slice phase as
        megascale send/recv host transfers, counted separately — the
        only groups that ride DCN as HLO collectives are explicit
        slice-spanning ones."""
        lo, hi = c.get("group_min"), c.get("group_max")
        if lo is None or hi is None:
            return False
        return lo // n_per_slice != hi // n_per_slice

    def wire_ms(c):
        """Ring-model wire time of one collective, over the link class
        its replica group actually rides (a group crossing a slice
        boundary goes over DCN). Result-shape bytes B:
        all-reduce 2(g-1)/g·B; all-gather (g-1)/g·B;
        reduce-scatter (g-1)·B (the result is the 1/g shard)."""
        g = c.get("group_size") or n_per_slice
        dcn = crosses_dcn(c)
        bw = (args.dcn_gbps if dcn else args.ici_gbps) * 1e9
        factor = _WIRE_FACTOR[c.get("op", "all-reduce")](g)
        return factor * c["bytes"] / bw * 1e3, dcn

    grad_bytes = sum(w["bytes"] for w in sched["async_windows"]) + \
        sum(s["bytes"] for s in sched["sync_all_reduces"])
    t_comm_ms, t_dcn_ms = 0.0, 0.0
    dcn_collectives = []
    for s_ in sched["sync_all_reduces"]:
        t, dcn = wire_ms(s_)
        s_["crosses_dcn"] = dcn
        t_comm_ms += t
        if dcn:
            t_dcn_ms += t
            dcn_collectives.append(s_)
    # megascale DCN phase (multi-slice): the send payloads, one-way
    ms_bytes = sched.get("megascale_send_bytes", 0)
    if ms_bytes:
        t = ms_bytes / (args.dcn_gbps * 1e9) * 1e3
        t_comm_ms += t
        t_dcn_ms += t
    for w in sched["async_windows"]:
        t, dcn = wire_ms(w)
        w["crosses_dcn"] = dcn
        t_comm_ms += t
        if dcn:
            t_dcn_ms += t
            dcn_collectives.append(w)
    step_ms = args.single_chip_ms
    # pessimistic bound: every collective fully serializes after the
    # compute (zero overlap)
    eff_no_overlap = step_ms / (step_ms + t_comm_ms)
    # optimistic bound: communication fully hidden behind compute
    eff_full_overlap = step_ms / max(step_ms, t_comm_ms)

    total_ops = max(1, sched["total_compute_ops"])
    if sched["async_windows"]:
        # async-pair mode: charge each window only the wire time its
        # in-window compute cannot cover (equal-share op cost — crude
        # but conservative for ResNet backward windows)
        ms_per_op = step_ms / total_ops
        t_exposed = 0.0
        for w in sched["async_windows"]:
            t_wire = wire_ms(w)[0]
            t_exposed += max(0.0, t_wire - w["compute_ops_inside"]
                             * ms_per_op)
        for s_ in sched["sync_all_reduces"]:
            t_exposed += wire_ms(s_)[0]
        hidden_frac = 1.0 - t_exposed / t_comm_ms if t_comm_ms else 0.0
        eff_sched = step_ms / (step_ms + t_exposed)
    else:
        # sync-op schedule (this XLA build): placement evidence. A
        # collective with compute scheduled AFTER it in the instruction
        # stream is overlappable by the runtime (the transfer proceeds
        # while later fusions run); bytes at the schedule tail cannot
        # overlap anything.
        t_exposed = sum(wire_ms(s_)[0]
                        for s_ in sched["sync_all_reduces"]
                        if s_["compute_ops_after"] < 2)
        # megascale DCN sends: overlap unknown from the text — charge
        # them as fully exposed (conservative)
        if ms_bytes:
            t_exposed += ms_bytes / (args.dcn_gbps * 1e9) * 1e3
        overlappable = sum(s_["bytes"]
                           for s_ in sched["sync_all_reduces"]
                           if s_["compute_ops_after"] >= 2)
        hidden_frac = overlappable / grad_bytes if grad_bytes else 0.0
        eff_sched = step_ms / (step_ms + t_exposed)

    # hierarchical-DCN contract (multi-slice + zero>=1): nothing bigger
    # than a 1/n_ici shard crosses the slice boundary. XLA bundles the
    # cross-slice phase into one megascale transfer of ALL grad shards,
    # so the bound is total-param-bytes/n_ici: a hierarchical transfer
    # sits at exactly that, while a full-gradient DCN phase would show
    # >= largest_param (single grad, un-reduce-scattered) or
    # total_param (bundled) — both over the bound for n_ici >= 2.
    largest_dcn = max(
        [c["bytes"] for c in dcn_collectives] +
        [sched.get("megascale_send_max_bytes", 0)] + [0])
    dcn_bytes_total = (sum(c["bytes"] for c in dcn_collectives)
                       + ms_bytes)
    hierarchical_ok = None
    shard_bound = None
    if args.num_slices > 1 and param_info:
        shard_bound = param_info["total"] / max(1, n_per_slice)
        hierarchical_ok = bool(
            largest_dcn <= shard_bound * 1.05 + 4096
            and dcn_bytes_total <= shard_bound * (
                2.10 + 0.05) + 8192)
        # dcn_bytes_total bound: the reduce phase (shards in) + the
        # broadcast phase (reduced shards out) = 2x one shard set

    result = {
        "topology": args.topology, "num_slices": args.num_slices,
        "model": args.model,
        "zero_stage": args.zero,
        "n_chips": n,
        "batch_per_chip": args.batch_per_chip,
        "global_batch": args.batch_per_chip * n,
        "async_all_reduces": n_async,
        "async_with_compute_inside": len(overlapped),
        "compute_ops_inside_windows": ops_inside,
        "sync_collectives": len(sched["sync_all_reduces"]),
        "collective_op_counts": {
            op: sum(1 for s_ in sched["sync_all_reduces"]
                    if s_.get("op") == op)
            for op in ("all-reduce", "reduce-scatter", "all-gather")},
        "grad_collective_bytes": grad_bytes,
        "megascale_dcn_sends": sched.get("megascale_sends", 0),
        "megascale_dcn_bytes": ms_bytes,
        "dcn_crossing_collectives": len(dcn_collectives),
        "dcn_collective_bytes": dcn_bytes_total,
        "largest_dcn_collective_bytes": largest_dcn,
        "largest_param_bytes": (param_info or {}).get("largest"),
        "total_param_bytes": (param_info or {}).get("total"),
        "dcn_shard_bound_bytes": shard_bound,
        "hierarchical_ok": hierarchical_ok,
        "wire_time_ms": round(t_comm_ms, 3),
        "wire_time_dcn_ms": round(t_dcn_ms, 3),
        "single_chip_step_ms": step_ms,
        "overlappable_bytes_fraction": round(hidden_frac, 4),
        "dp_efficiency_no_overlap": round(eff_no_overlap, 4),
        "dp_efficiency_full_overlap": round(eff_full_overlap, 4),
        "dp_efficiency_scheduled": round(eff_sched, 4),
        "total_compute_ops": sched["total_compute_ops"],
        "unparsed_replica_groups": len(sched["unparsed_replica_groups"]),
    }
    if sched["unparsed_replica_groups"]:
        print(f"WARNING: {len(sched['unparsed_replica_groups'])} "
              f"collective(s) with unparsed replica_groups — the wire "
              f"model assumed all-devices-over-ICI for them (see "
              f"`unparsed_replica_groups` in the artifact)",
              file=sys.stderr)
    print(json.dumps(result, indent=2))
    slug = args.topology.replace(":", "_") + (
        f"_x{args.num_slices}" if args.num_slices > 1 else "") + (
        f"_{args.model}" if args.model != "resnet50" else "") + (
        f"_zero{args.zero}" if args.zero else "")
    out = args.out or os.path.join(
        REPO, "benchmarks", "runs", f"scaling_aot_{slug}.json")
    sync_tail = sorted(sched["sync_all_reduces"],
                       key=lambda s: -s["bytes"])[:40]
    with open(out, "w") as f:
        json.dump({**result, "windows": sched["async_windows"],
                   "largest_sync_all_reduces": sync_tail,
                   "dcn_crossing_detail": sorted(
                       dcn_collectives, key=lambda s: -s["bytes"])[:20],
                   "unparsed_replica_group_lines":
                       sched["unparsed_replica_groups"]}, f, indent=2)
    print(f"wrote {out}")


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    main()
