"""Where the device waited, by what the program was doing.

    python3 benchmarks/tools/gaps.py TRACE [TOP]

TRACE is a profiler trace (``*.xplane.pb``, gzipped or not) or a
fixture that ``harness/program_spans.py`` saved.  Prints the devices'
idle time inside the traced window by the innermost span the serve
thread was in (the program's ``ra.*`` spans beneath the benchmark's
five), with each span's self time on that thread, and under each of the
TOP largest what the other threads (WAL shard workers and writers) ran
meanwhile; then the fused step's device time by stage.  For PERF.md's
tables.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv) -> int:
    from benchmarks.harness import program_spans as ps
    from benchmarks.harness.serve import SPANS
    path = argv[0]
    top = int(argv[1]) if len(argv) > 1 else 6
    loaded = ps.load_fixture(path) if path.endswith(".json.gz") \
        else ps.load(path, SPANS)
    gaps = ps.idle_gaps(loaded)
    if not gaps:
        print("gaps: no device operation or no host span in the trace")
        return 1
    print(f"window {gaps['window_s']:.3f} s, idle {gaps['idle_s']:.3f} s "
          f"({100 * gaps['idle_s'] / gaps['window_s']:.1f}%), "
          f"{len(loaded['devices'])} device(s)")
    serve = ps.line_of(loaded, "ra.pump") or []
    self_s = {}
    for s, e, name in ps.innermost_segments(serve):
        self_s[name] = self_s.get(name, 0) + (e - s) / 1e9
    print(f"{'idle s':>9} {'share':>6} {'self s':>8}  serve thread's "
          "innermost span")
    for i, (name, sec) in enumerate(gaps["by_span"].items()):
        print(f"{sec:9.4f} {100 * sec / gaps['idle_s']:5.1f}% "
              f"{self_s.get(name, 0):8.4f}  {name}")
        if i < top:
            for other, osec in list(
                    gaps["meanwhile"].get(name, {}).items())[:4]:
                print(f"{'':26}   meanwhile {osec:8.4f} s  {other}")
    for span in ("ra.pump", "ra.sweep"):
        pct = ps.self_pct(loaded, span)
        if pct is not None:
            print(f"{span}: {pct:.2f}% of its time under no child span")
    stages = ps.step_stages(loaded)
    if stages:
        tot = stages["total_s"]
        print(f"step: {stages['dispatches']} dispatches, {tot:.4f} s of "
              "device time")
        for name, sec in sorted(stages["stages"].items(),
                                key=lambda kv: -kv[1]):
            print(f"{sec:9.4f} {100 * sec / tot:5.1f}%  {name}")
        for name, sec in stages["unnamed"][:3]:
            print(f"{sec:9.4f} {100 * sec / tot:5.1f}%  (no stage) "
                  f"{name[:70]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
