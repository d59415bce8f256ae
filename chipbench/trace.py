"""From a profiler trace to numbers: device busy time, per-name device
time, the breakdown.

``reduce_file`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain planes; ``reduce_planes`` does the arithmetic on plain data,
so the tests check it on a small recorded trace without a profiler.

A plain plane is ``{"name": str, "lines": [{"name": str, "events":
[[name, start_ns, duration_ns, module], ...]}]}``.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip
named ``/device:TPU:<n>``; its line ``XLA Ops`` carries one event per
executed HLO op or fusion (Pallas kernels appear as custom calls under
their call-site name), ``XLA Modules`` one event per executed program
named ``<module>(<fingerprint>)``. Every program that comes out of a
serving artifact is called ``jit_call_exported``: only the fingerprint
tells the decode program from the sixteen prefill programs. Ops inside
a ``while`` (the layer scan) are events of their own beside the
``while`` itself, so per-op sums overlap; the busy time is the UNION.
Pallas kernels are ``custom-call``s with the target ``tpu_custom_call``
under the name of their call site (``%closed_call.3``,
``%call_exported.1``): no name of their own. Host threads sit in
``/host:CPU``.
"""

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union_seconds(intervals) -> float:
    """Total length of the union of [start, end) intervals given in ns."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def module_base(name: str) -> str:
    """``jit_decode_fn(123456)`` -> ``jit_decode_fn``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_label(name: str) -> str:
    """An op event carries its whole HLO line; the label is its result
    name and opcode: ``%while.5 while``, ``%closed_call.3 custom-call
    tpu_custom_call``."""
    m = re.match(r"^(%[^\s=]+) = ", name)
    if not m:
        return name[:80]
    op = re.search(r"\s([a-z][a-z\-]*)\(", name)
    label = m.group(1) + (" " + op.group(1) if op else "")
    if 'custom_call_target="tpu_custom_call"' in name:
        label += " tpu_custom_call"
    return label


def reduce_planes(planes, chips: int) -> dict:
    dev = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    dev = sorted(dev, key=lambda p: p["name"])[:chips] if chips else dev
    busy, ops, modules, ops_by_module = [], {}, {}, {}
    gaps = []
    for p in dev:
        spans = []
        mod_spans = []
        for ln in p["lines"]:
            if ln["name"] == MODULES_LINE:
                for name, s, d, _ in ln["events"]:
                    # keyed WITH the fingerprint: exported programs all
                    # share the base name ``jit_call_exported``
                    t = modules.setdefault(name, [0.0, 0])
                    t[0] += d / 1e9
                    t[1] += 1
                    mod_spans.append((s, s + d, name))
        mod_spans.sort()
        for ln in p["lines"]:
            if ln["name"] != OPS_LINE:
                continue
            starts = [m[0] for m in mod_spans]
            for name, s, d, module in ln["events"]:
                spans.append((s, s + d))
                name = op_label(name)
                t = ops.setdefault(name, [0.0, 0])
                t[0] += d / 1e9
                t[1] += 1
                if not module and mod_spans:
                    i = bisect.bisect_right(starts, s) - 1
                    if i >= 0 and s < mod_spans[i][1]:
                        module = mod_spans[i][2]
                if module:
                    t = ops_by_module.setdefault(module, {}).setdefault(
                        name, [0.0, 0])
                    t[0] += d / 1e9
                    t[1] += 1
        if not spans:       # a plane with modules only still was busy
            spans = [(s, e) for s, e, _ in mod_spans]
        busy.append(union_seconds(spans))
        m = merged(spans)
        for (_, e0), (s1, _) in zip(m, m[1:]):
            gaps.append((s1 - e0, e0, s1))
    host = [p for p in planes if p["name"].startswith("/host:")]
    gaps.sort(reverse=True)
    idle = []
    for length, g0, g1 in gaps[:10]:
        idle.append([_host_during(host, g0, g1), length / 1e9])
    top = sorted(ops.items(), key=lambda kv: -kv[1][0])[:10]
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "chips_traced": len(dev),
        "ops": ops, "modules": modules, "ops_by_module": ops_by_module,
        "breakdown": {"device_ops": [[n, t[0]] for n, t in top],
                      "idle_gaps": idle}}


def _host_during(host_planes, g0, g1) -> str:
    """The host event that covers most of the gap [g0, g1)."""
    best, best_cov = "host: nothing recorded", 0
    for p in host_planes:
        for ln in p["lines"]:
            for name, s, d, _ in ln["events"]:
                cov = min(g1, s + d) - max(g0, s)
                if cov > best_cov:
                    best, best_cov = f"host: {name}", cov
    return best


def planes_of(profile) -> list:
    """``jax.profiler.ProfileData`` -> plain planes (device planes whole,
    host planes without the zero-length bookkeeping events)."""
    out = []
    for pl in profile.planes:
        is_dev = bool(DEVICE_PLANE.match(pl.name))
        if not (is_dev or pl.name.startswith("/host:")):
            continue
        lines = []
        for ln in pl.lines:
            if is_dev and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for e in ln.events:
                d = e.duration_ns
                if not is_dev and d <= 0:
                    continue
                module = ""
                if is_dev and ln.name == OPS_LINE:
                    for k, v in e.stats:
                        if k == "hlo_module":
                            module = str(v)
                            break
                evs.append([e.name, int(e.start_ns), int(d), module])
            lines.append({"name": ln.name, "events": evs})
        out.append({"name": pl.name, "lines": lines})
    return out


def reduce_file(path: str, chips: int) -> dict:
    import jax
    profile = jax.profiler.ProfileData.from_file(path)
    return reduce_planes(planes_of(profile), chips)


def idle_pct(summary: dict):
    """1 - busy / traced stretch, in percent; nothing without a stretch."""
    if summary["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
