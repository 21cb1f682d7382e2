"""The program's own spans and the device's stage names, from the
profiler's trace.

``trace_reduce`` reads the trace through ``jax.profiler.ProfileData``,
which merges nothing but also shows neither a line's id (every Python
thread's line is named after the interpreter) nor an operation's
metadata (the ``jax.named_scope`` path the compiler keeps as
``op_name``, the program an operation belongs to).  This loader reads
the ``.xplane.pb`` itself, with ``google.protobuf`` and the handful of
messages declared below, into plain data that a fixture can hold:

    {"host": [{"line": id, "events": [[name, start_ns, dur_ns, args]]}],
     "devices": {plane: {"modules": [[name, program_id, start_ns, dur_ns]],
                         "ops": [[name, program_id, scope, start_ns,
                                  dur_ns]]}},
     "fused_stages": {program_id: {instruction: stage}}}

Of the host it keeps the program's spans (``ra.*``) and the spans named
by the caller (the benchmark's five), each thread a line of its own.
``scope`` is the operation's ``op_name`` as the trace has it (``tf_op``).
``fused_stages`` comes from the step module's own HLO, which the
profiler puts in the trace (plane ``/host:metadata``): for a fusion
whose own ``op_name`` names no stage (the compiler rewrote its root,
a batched scatter for one), the stage that the instructions fused into
it name.

Against a program that has no such spans or scopes (the parent of the
PR that added them) every reduction below returns None.
"""
from __future__ import annotations

import gzip
import json
import re

from . import trace_reduce

PROGRAM_PREFIX = "ra."
#: the fused step's module, by the name the program gives its jit
STEP_MODULE = "ra_superstep"
#: a stage's scope inside an operation's op_name path
_STAGE = re.compile(r"(?:^|/)(ra\.(?:s\d\w*|durable_compact))(?=/|$)")

#: the plane that holds each module's HLO, and the stat it is under
HLO_PLANE, HLO_STAT = "/host:metadata", "Hlo Proto"

_MESSAGES = None


def _messages() -> dict:
    """The XSpace message (tsl/profiler/protobuf/xplane.proto) and the
    HloProto one (xla/service/hlo.proto), declared here field by field:
    only what the reductions read.  A map is, on the wire, a repeated
    entry of key and value."""
    global _MESSAGES
    if _MESSAGES is not None:
        return _MESSAGES
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto2")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                f.type, f.type_name = F.TYPE_MESSAGE, \
                    f".bench_xplane.{ftype}"
            else:
                f.type = ftype

    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False),
            ("double_value", 2, F.TYPE_DOUBLE, False),
            ("uint64_value", 3, F.TYPE_UINT64, False),
            ("int64_value", 4, F.TYPE_INT64, False),
            ("str_value", 5, F.TYPE_BYTES, False),
            ("bytes_value", 6, F.TYPE_BYTES, False),
            ("ref_value", 7, F.TYPE_UINT64, False))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, False),
            ("offset_ps", 2, F.TYPE_INT64, False),
            ("duration_ps", 3, F.TYPE_INT64, False),
            ("stats", 4, "XStat", True))
    message("XLine", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_BYTES, False),
            ("timestamp_ns", 3, F.TYPE_INT64, False),
            ("events", 4, "XEvent", True))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_BYTES, False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_BYTES, False))
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XStatMetadata", False))
    message("XPlane", ("name", 2, F.TYPE_BYTES, False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    message("OpMetadata", ("op_name", 2, F.TYPE_BYTES, False))
    message("HloInstruction", ("name", 1, F.TYPE_BYTES, False),
            ("opcode", 2, F.TYPE_BYTES, False),
            ("metadata", 7, "OpMetadata", False),
            ("called_computation_ids", 38, F.TYPE_INT64, True))
    message("HloComputation", ("name", 1, F.TYPE_BYTES, False),
            ("instructions", 2, "HloInstruction", True),
            ("id", 5, F.TYPE_INT64, False))
    message("HloModule", ("name", 1, F.TYPE_BYTES, False),
            ("computations", 3, "HloComputation", True))
    message("HloProto", ("hlo_module", 1, "HloModule", False))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _MESSAGES = {
        name: message_factory.GetMessageClass(
            pool.FindMessageTypeByName("bench_xplane." + name))
        for name in ("XSpace", "HloProto")}
    return _MESSAGES


def _text(b: bytes) -> str:
    return b.decode("utf-8", "replace")


def _stat_values(stats, stat_names: dict) -> dict:
    out = {}
    for st in stats:
        key = stat_names.get(st.metadata_id)
        if key is None:
            continue
        if st.HasField("int64_value"):
            out[key] = st.int64_value
        elif st.HasField("uint64_value"):
            out[key] = st.uint64_value
        elif st.HasField("str_value"):
            out[key] = _text(st.str_value)
        elif st.HasField("ref_value"):
            out[key] = stat_names.get(st.ref_value, "")
        elif st.HasField("double_value"):
            out[key] = st.double_value
    return out


def fused_stages(module) -> dict:
    """{instruction: stage} for the fusions of one HLO module whose own
    op_name names no stage and whose fused instructions do: the stage
    that more of them name than any other (the first by name on a
    tie)."""
    inner = {c.id: c for c in module.computations}
    out = {}
    for comp in module.computations:
        for ins in comp.instructions:
            if ins.opcode != b"fusion" or \
                    stage_of(_text(ins.metadata.op_name)):
                continue
            votes = {}
            for cid in ins.called_computation_ids:
                for sub in inner[cid].instructions:
                    st = stage_of(_text(sub.metadata.op_name))
                    if st:
                        votes[st] = votes.get(st, 0) + 1
            if votes:
                out[_text(ins.name)] = min(
                    votes, key=lambda st: (-votes[st], st))
    return out


def _program_of(module_name: str) -> str:
    """A module's program is the number its name ends in."""
    return module_name.rpartition("(")[2].rstrip(")")


def load(path: str, span_names=()) -> dict:
    """See the module's docstring."""
    from google.protobuf.message import DecodeError
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = _messages()["XSpace"].FromString(f.read())
    want = set(span_names)
    host, devices, fused = [], {}, {}
    for plane in space.planes:
        pname = _text(plane.name)
        if pname == HLO_PLANE:
            stat_ids = {e.key for e in plane.stat_metadata
                        if _text(e.value.name) == HLO_STAT}
            for e in plane.event_metadata:
                mname = _text(e.value.name)
                if STEP_MODULE not in mname:
                    continue
                for st in e.value.stats:
                    if st.metadata_id not in stat_ids:
                        continue
                    try:
                        hlo = _messages()["HloProto"].FromString(
                            st.bytes_value)
                    except DecodeError:     # not the HLO this declares
                        continue
                    fused[_program_of(mname)] = fused_stages(
                        hlo.hlo_module)
            continue
        is_dev = pname.startswith(trace_reduce.DEVICE_PREFIX)
        if not is_dev and not pname.startswith("/host:CPU"):
            continue
        stat_names = {e.key: _text(e.value.name)
                      for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        names = {k: _text(m.name) for k, m in meta.items()}
        if is_dev:
            op_meta = {k: _stat_values(m.stats, stat_names)
                       for k, m in meta.items()}
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                lname = _text(line.name)
                if lname not in (trace_reduce.OPS_LINE,
                                 trace_reduce.MODULES_LINE):
                    continue
                t0 = line.timestamp_ns
                for ev in line.events:
                    om = op_meta[ev.metadata_id]
                    start = t0 + ev.offset_ps // 1000
                    dur = ev.duration_ps // 1000
                    name = names[ev.metadata_id]
                    if lname == trace_reduce.MODULES_LINE:
                        dev["modules"].append(
                            [name, _program_of(name), start, dur])
                    else:
                        dev["ops"].append(
                            [name[:trace_reduce.OP_NAME_CHARS],
                             str(om.get("program_id", "")),
                             om.get("tf_op", ""), start, dur])
            if dev["ops"]:
                devices[pname] = dev
            continue
        keep = {k for k, n in names.items()
                if n.startswith(PROGRAM_PREFIX) or n in want}
        for line in plane.lines:
            t0 = line.timestamp_ns
            ev = [[names[e.metadata_id], t0 + e.offset_ps // 1000,
                   e.duration_ps // 1000,
                   _stat_values(e.stats, stat_names)]
                  for e in line.events if e.metadata_id in keep]
            if ev:
                host.append({"line": line.id, "events": ev})
    return {"host": host, "devices": devices, "fused_stages": fused}


def save_fixture(loaded: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(loaded, f)


def load_fixture(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def of_run(run):
    """The run's trace, loaded once and kept on the run; None where the
    run was not traced, wrote no trace, or this installation cannot
    read one."""
    if not hasattr(run, "_program_spans"):
        loaded = None
        path = trace_reduce.find_xplane(run.trace_dir) if run.trace \
            else None
        if path:
            try:
                from .serve import SPANS
                loaded = load(path, SPANS)
            except ImportError:
                loaded = None
            # as run.py keeps the accepted reduction's fixture
            if loaded and "keep_trace" in run.mix:
                save_fixture(loaded, run.mix["keep_trace"] + ".spans.json.gz")
        run._program_spans = loaded
    return run._program_spans


def stages_of_run(run):
    """:func:`step_stages` of the run's trace, computed once a run (a
    dozen readers ask); None where :func:`of_run` or it reads nothing."""
    if not hasattr(run, "_step_stages"):
        loaded = of_run(run)
        run._step_stages = step_stages(loaded) if loaded else None
    return run._step_stages


# -- host: self time ---------------------------------------------------------

def line_of(loaded: dict, span: str):
    """The events of the thread that ran ``span`` (the serve thread for
    ``ra.pump`` and ``ra.sweep``), or None."""
    for line in loaded["host"]:
        if any(e[0] == span for e in line["events"]):
            return line["events"]
    return None


def self_pct(loaded: dict, span: str):
    """Of the time inside ``span``, the share that no other program
    span on the same thread covers: the span's self time over its
    time, in percent, over every occurrence in the trace."""
    events = line_of(loaded, span)
    if not events:
        return None
    parents = [(s, s + d) for n, s, d, _a in events if n == span]
    inner = sorted((s, s + d) for n, s, d, _a in events
                   if n != span and n.startswith(PROGRAM_PREFIX))
    total = covered = 0
    for lo, hi in parents:
        total += hi - lo
        merged = trace_reduce._union(
            (max(s, lo), min(e, hi)) for s, e in inner
            if s < hi and e > lo)
        covered += sum(e - s for s, e in merged)
    if total <= 0:
        return None
    return 100.0 * (total - covered) / total


# -- device: stages ----------------------------------------------------------

def stage_of(scope: str) -> str:
    """The stage an operation's op_name path names ('' for none): the
    innermost ``ra.s*`` / ``ra.durable_compact`` component."""
    found = _STAGE.findall(scope)
    return found[-1] if found else ""


def _instruction_of(op_name: str) -> str:
    """``%fusion.230 = s32[...] fusion(...)`` -> ``fusion.230``."""
    return op_name.partition(" ")[0].lstrip("%")


def _self_times(ops):
    """(operation, self ns) of operations on one line: an operation's
    time less the time of the operations nested in it (a while loop
    holds its body's fusions)."""
    out, stack = [], []          # stack of [end, index into out]
    for op in sorted(ops, key=lambda o: (o[3], -o[4])):
        start, dur = op[3], op[4]
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([op, dur])
        stack.append([start + dur, len(out) - 1])
    return out


def step_stages(loaded: dict):
    """Device time of the fused step by stage: {"dispatches": runs of the
    step's module, "total_s": its operations' time, "stages": {stage:
    seconds}, "unnamed": [(operation, seconds)] largest first}, each the
    mean over the device planes.  None where no module carries the
    step's name.  An operation counts under the stage its own op_name
    names, else under the one ``fused_stages`` gives it, else under
    none."""
    fused = loaded.get("fused_stages", {})
    per_dev = []
    for dev in loaded["devices"].values():
        progs = {p for n, p, _s, _d in dev["modules"] if STEP_MODULE in n}
        if not progs:
            continue
        stages, unnamed, total = {}, {}, 0
        for op, self_ns in _self_times(
                [o for o in dev["ops"] if o[1] in progs]):
            total += self_ns
            stage = stage_of(op[2]) or fused.get(op[1], {}).get(
                _instruction_of(op[0]), "")
            if stage:
                stages[stage] = stages.get(stage, 0) + self_ns
            else:
                unnamed[op[0]] = unnamed.get(op[0], 0) + self_ns
        per_dev.append((sum(1 for n, p, _s, _d in dev["modules"]
                            if p in progs), total, stages, unnamed))
    if not per_dev:
        return None
    n = len(per_dev)
    stages, unnamed = {}, {}
    for _disp, _tot, st, un in per_dev:
        for k, v in st.items():
            stages[k] = stages.get(k, 0) + v / n / 1e9
        for k, v in un.items():
            unnamed[k] = unnamed.get(k, 0) + v / n / 1e9
    return {"dispatches": max(d for d, _t, _s, _u in per_dev),
            "total_s": sum(t for _d, t, _s, _u in per_dev) / n / 1e9,
            "stages": stages,
            "unnamed": sorted(unnamed.items(), key=lambda kv: -kv[1])}


# -- idle gaps by innermost span ---------------------------------------------

def innermost_segments(events) -> list:
    """One thread's spans flattened: [(start, end, name)] that do not
    overlap, each stretch of time under the innermost span that covers
    it (a span's self time is the stretches that carry its name)."""
    out, stack = [], []          # stack of (end, name), innermost last
    cursor = 0

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for name, start, dur, _args in sorted(events,
                                          key=lambda e: (e[1], -e[2])):
        close_until(start)
        if stack and start > cursor:
            out.append((cursor, start, stack[-1][1]))
        cursor = max(cursor, start)
        stack.append((start + dur, name))
    close_until(float("inf"))
    return out


def _overlap(segments, lo, hi):
    """(name, ns) for the parts of [lo, hi) each segment covers."""
    for s, e, name in segments:
        if s < hi and e > lo:
            yield name, min(e, hi) - max(s, lo)


def idle_gaps(loaded: dict) -> dict:
    """The devices' idle time inside the traced window, by the
    innermost span the serve thread was in, and for each of those what
    the other threads ran meanwhile: {"window_s", "idle_s", "by_span":
    {span: seconds}, "meanwhile": {span: {other thread's span:
    seconds}}}, device seconds the mean over the device planes.  The
    serve thread is the one that ran ``ra.pump``, else the one with the
    most spans."""
    devs = loaded["devices"]
    if not devs or not loaded["host"]:
        return {}
    t_lo = min(o[3] for d in devs.values() for o in d["ops"])
    t_hi = max(o[3] + o[4] for d in devs.values() for o in d["ops"])
    serve = line_of(loaded, PROGRAM_PREFIX + "pump") or max(
        (ln["events"] for ln in loaded["host"]), key=len)
    serve_seg = innermost_segments(serve)
    others = [innermost_segments(ln["events"]) for ln in loaded["host"]
              if ln["events"] is not serve]
    by_span, meanwhile, idle = {}, {}, 0
    for d in devs.values():
        busy = trace_reduce._union((o[3], o[3] + o[4]) for o in d["ops"])
        edges = [t_lo] + [t for se in busy for t in se] + [t_hi]
        for lo, hi in zip(edges[::2], edges[1::2]):
            if hi <= lo:
                continue
            idle += hi - lo
            covered = 0
            for s, e, name in serve_seg:
                if s >= hi or e <= lo:
                    continue
                a, b = max(s, lo), min(e, hi)
                covered += b - a
                by_span[name] = by_span.get(name, 0) + b - a
                for seg in others:
                    for other, ns in _overlap(seg, a, b):
                        m = meanwhile.setdefault(name, {})
                        m[other] = m.get(other, 0) + ns
            if hi - lo > covered:
                by_span["none"] = by_span.get("none", 0) \
                    + hi - lo - covered
    n = len(devs) * 1e9
    return {"window_s": (t_hi - t_lo) / 1e9, "idle_s": idle / n,
            "by_span": {k: v / n for k, v in sorted(
                by_span.items(), key=lambda kv: -kv[1])},
            "meanwhile": {k: {o: v / n for o, v in sorted(
                m.items(), key=lambda kv: -kv[1])}
                for k, m in meanwhile.items()}}
