"""Several runs of the benchmark in one call on the machine with the
chip, one process each and one after the other (a chip belongs to one
process), every result line appended to a file.

    python3 benchmarks/tools/many.py OUT.jsonl -- ARGS [-- ARGS ...]

Each ARGS is one run's arguments to ``run.py``, or to ``control.py``
where it starts with ``--precision``.  A run's standard error
goes to ``OUT.jsonl.err``.  Exits with the number of runs that failed.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")
CONTROL = os.path.join(os.path.dirname(HERE), "control.py")


def main(argv) -> int:
    out_path, rest = argv[0], argv[1:]
    runs, cur = [], []
    for a in rest:
        if a == "--":
            if cur:
                runs.append(cur)
            cur = []
        else:
            cur.append(a)
    if cur:
        runs.append(cur)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    failed = 0
    for args in runs:
        t0 = time.time()
        with open(out_path + ".err", "a") as err:
            err.write(f"=== {' '.join(args)}\n")
            err.flush()
            prog = CONTROL if args[0] == "--precision" else RUN
            p = subprocess.run([sys.executable, prog] + args,
                               stdout=subprocess.PIPE, stderr=err, text=True)
        lines = p.stdout.strip().splitlines()
        rec = {"args": args, "rc": p.returncode,
               "wall_s": round(time.time() - t0, 3)}
        try:
            rec["result"] = json.loads(lines[-1])
        except (IndexError, ValueError):
            rec["stdout_tail"] = lines[-3:]
        failed += p.returncode != 0
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        r = rec.get("result", {})
        print(json.dumps({"args": args, "rc": rec["rc"],
                          "wall_s": rec["wall_s"],
                          "correct": r.get("correct"),
                          "attempted": r.get("attempted"),
                          "failed": r.get("failed"),
                          "metrics": {k: v["value"] for k, v in
                                      r.get("metrics", {}).items()},
                          "notes": r.get("notes"),
                          "device": r.get("device")}), flush=True)
    return failed


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
