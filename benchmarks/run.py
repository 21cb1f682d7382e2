"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's file by the cell's name, through it the
configuration's and the mix's, and for ``--trace 1`` each per-layer
metric's file and the reader it names.  Runs on the machine it is
started on, in one process; refuses anything but a TPU with the chips
the cell asks for (exit 2, no result line).  The last line of standard
output is the result; the numbers compared for ``correct`` are the last
lines of standard error and the last key of the result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: everything a run writes (WAL, trace) goes here, inside the checkout,
#: and is removed when the run ends; the compile cache is the program's
#: fixed ``<checkout>/.jax_cache``
RUN_ROOT = os.path.join(ROOT, ".bench_run")


def process_age_s() -> float:
    """Seconds since this process was started, from /proc: the start of
    set-up is the start of the interpreter, not of this module."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_reader(name: str):
    path = os.path.join(HERE, "readers", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_reader_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Ctx:
    """What a reader may look at."""

    def __init__(self, run, window, trace: dict, device_kind: str) -> None:
        self.run, self.window, self.trace = run, window, trace
        self.device_kind = device_kind


def device_stamp() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def per_layer(manifest: dict, cell: str, ctx: Ctx) -> dict:
    """Exactly the per-layer metrics whose list names the cell, less
    those whose reader found nothing to read."""
    out = {}
    for m in manifest["per_layer"]:
        if cell not in m["workloads"]:
            continue
        meta = load_json("metrics", m["name"] + ".json")
        value = load_reader(meta["reader"])(ctx, meta)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(args, manifest: dict, *, require_tpu: bool = True,
             tamper=None) -> tuple:
    """One run.  Returns (exit code, result or None).  ``require_tpu``
    and ``tamper`` are for the tests: the CPU rehearsal, and a fault
    planted where the payload is produced."""
    from benchmarks.harness import check, metrics, serve, trace_reduce

    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"run: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2, None
    cell = load_json("cells", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    # for the sweeps that find a cell's rate or pipe, never the driver's
    for item in args.override:
        where, _, rest = item.partition(".")
        key, _, value = rest.partition("=")
        {"cell": cell, "mix": mix}[where][key] = json.loads(value)

    stamp = device_stamp()
    if require_tpu and (stamp["platform"] != "tpu"
                        or stamp["count"] < cell["chips"]):
        print(f"run: refusing to run: JAX found {stamp['count']} x "
              f"{stamp['platform']} ({stamp['kind']}), the cell needs "
              f"{cell['chips']} TPU chip(s)", file=sys.stderr)
        return 2, None
    from ra_tpu.utils import enable_compile_cache
    enable_compile_cache()

    run = serve.Run(cell, config, mix, args.seed, args.seconds,
                    bool(args.trace),
                    os.path.join(RUN_ROOT, args.workload))
    run.tamper = tamper
    try:
        run.set_up()
        age, t_age = process_age_s(), time.perf_counter()
        run.measure()
        setup_s = age + (run.t0 - t_age)
        window = metrics.Window(run)
        trace = {}
        if run.trace:
            path = trace_reduce.find_xplane(run.trace_dir)
            if path:
                planes = trace_reduce.load(path, serve.SPANS)
                trace = trace_reduce.reduce(planes)
                if "keep_trace" in mix:
                    trace_reduce.save_fixture(planes, mix["keep_trace"])
        device = dict(stamp, memory_peak_bytes=run.memory_peak)
        if run.trace:
            if trace.get("busy_s"):
                device["busy_s"] = trace["busy_s"]
                device["window_s"] = trace["window_s"]
            elif require_tpu:
                print("run: the traced window holds no device operation",
                      file=sys.stderr)
                return 1, None
            values = per_layer(manifest, args.workload,
                               Ctx(run, window, trace, stamp["kind"]))
        else:
            units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
            values = {k: {"value": float(v), "unit": units[k]}
                      for k, v in metrics.end_to_end(run, window,
                                                     setup_s).items()}
        snaps = run.finish()
        compared = check.compare(run, snaps)
    finally:
        run.close()
    result = {"correct": check.verdict(compared),
              "attempted": window.attempted, "failed": window.failed,
              "metrics": values, "device": device}
    if run.trace:
        result["breakdown"] = {"device_ops": trace.get("device_ops", []),
                               "idle_gaps": trace.get("idle_gaps", [])}
    result["notes"] = {"reopen_s": run.reopen_s,
                       "refusals": int(run.fleet.refusals),
                       "acked_in_window": window.acked_in_window,
                       "backlog_mid": window.backlog(
                           (window.t0 + window.t1) / 2),
                       "backlog_end": window.backlog(window.t1),
                       "overrides": args.override,
                       "cycles": run.counters1["cycles"]
                       - run.counters0["cycles"],
                       "spans_s": {k: window.delta("spans", k)
                                   for k in serve.SPANS},
                       "phases_p50_ms": {
                           k: v["p50_ms"] for k, v in run.phases.items()
                           if isinstance(v, dict) and v["count"]},
                       "slowest_cycles": sorted(
                           ((round(t - run.t0, 3), round(d, 3), parts)
                            for t, d, parts in run.cycle_log
                            if run.t0 <= t < run.t1),
                           key=lambda c: -c[1])[:3],
                       "dispatches": window.delta("pipeline",
                                                  "superstep_dispatches"),
                       "wal_io_path": run.wal["io_path"]}
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        print(f"compared {k} {v} limit {lim}", file=sys.stderr)
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--override", action="append", default=[],
                    metavar="cell.KEY=JSON|mix.KEY=JSON",
                    help="sweeps only: one value of the cell's or the "
                    "mix's file for this run")
    args = ap.parse_args(argv)
    from benchmarks import manifest as mf
    try:
        import ra_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"run: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    manifest = mf.committed()
    mf.validate(manifest)
    rc, result = run_cell(args, manifest)
    if result is not None:
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
