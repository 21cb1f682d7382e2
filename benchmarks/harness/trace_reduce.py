"""From a profiler trace to the device's numbers.

Two stages, so that the arithmetic can be checked against a small
recorded trace (``benchmarks/fixtures/``) without a chip:

``load(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain data: ``{plane: {line: [(name, start_ns, dur_ns), ...]}}``,
keeping the device planes' op and module lines and, of the host, only
the benchmark's own spans.

``reduce(planes, spans)`` computes, per device: the busy time as the
union of the op intervals, the window as first op start to last op end
over all devices, the device time of the step program (the module that
takes most of the device's time: the fused step carries no name of its
own in the trace, ``jit__unknown``, until ROADMAP D4 gives it one), the
operations that took most time,
and the longest idle gaps with the benchmark span the host was in at
the middle of each.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OP_NAME_CHARS = 120


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str, span_names=()) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    want = set(span_names)
    planes: dict = {}
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        lines: dict = {}
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            ev = [(e.name, int(e.start_ns), int(e.duration_ns))
                  for e in line.events
                  if is_dev or e.name in want]
            if ev:
                lines.setdefault(line.name, []).extend(ev)
        if lines:
            planes[plane.name] = lines
    return planes


def save_fixture(planes: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(planes, f)


def load_fixture(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {p: {ln: [tuple(e) for e in ev] for ln, ev in lines.items()}
            for p, lines in raw.items()}


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _span_at(host_spans, t_ns: int) -> str:
    for name, s, d in host_spans:
        if s <= t_ns < s + d:
            return name
    return "none"


def reduce(planes: dict) -> dict:
    """See the module's docstring.  Returns nothing where no operation
    ran on a device."""
    dev = {p: ln for p, ln in planes.items()
           if p.startswith(DEVICE_PREFIX) and ln.get(OPS_LINE)}
    if not dev:
        return {}
    host_spans = sorted(
        ((n, s, d) for p, ln in planes.items()
         if not p.startswith(DEVICE_PREFIX)
         for ev in ln.values() for n, s, d in ev),
        key=lambda e: e[1])
    t_lo = min(s for ln in dev.values() for _, s, _d in ln[OPS_LINE])
    t_hi = max(s + d for ln in dev.values() for _, s, d in ln[OPS_LINE])
    window_ns = t_hi - t_lo
    busy_ns, step_ns, steps = {}, {}, {}
    op_ns: dict = {}
    gaps = []
    for p, ln in dev.items():
        merged = _union((s, s + d) for _, s, d in ln[OPS_LINE])
        busy_ns[p] = sum(e - s for s, e in merged)
        by_module: dict = {}
        for n, _s, d in ln.get(MODULES_LINE, ()):
            tot = by_module.setdefault(n, [0, 0])
            tot[0] += d
            tot[1] += 1
        step_ns[p], steps[p] = max(by_module.values(), default=(0, 0))
        step_name = max(by_module, key=lambda n: by_module[n][0],
                        default="")
        for n, _s, d in ln[OPS_LINE]:
            # the trace names an op by its whole HLO line: its head says
            # which op it is
            op_ns[n[:OP_NAME_CHARS]] = op_ns.get(n[:OP_NAME_CHARS], 0) + d
        edges = [t_lo] + [t for se in merged for t in se] + [t_hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) // 2))
    n_dev = len(dev)
    by_span: dict = {}
    for g, mid in sorted(gaps, reverse=True)[:2000]:
        name = _span_at(host_spans, mid)
        by_span[name] = by_span.get(name, 0) + g
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": n_dev,
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns.values()) / n_dev / 1e9,
        "busy_s_by_device": {p: v / 1e9 for p, v in busy_ns.items()},
        "step_s": sum(step_ns.values()) / n_dev / 1e9,
        "step_dispatches": max(steps.values()),
        "step_module": step_name,
        "device_ops": [[n, v / n_dev / 1e9] for n, v in top_ops],
        "idle_gaps": [[n, v / n_dev / 1e9] for n, v in top_gaps],
    }
