"""``BENCHMARK.json`` is derived, not written by hand.

    python3 benchmarks/manifest.py --write    # derive and write it
    python3 benchmarks/manifest.py --check    # derived == committed, and valid

The facts live one to a file: a cell in ``cells/<config>.<mix>.json``, a
deployment in ``configs/<name>.json``, a mix in ``traffic/<name>.json``
(with the end-to-end metrics its cells report), a per-layer metric in
``metrics/<name>.json`` (with the one end-to-end metric it ``moves``),
the end-to-end metrics and ``run_seconds`` in ``metrics/end_to_end.json``.
A cell reports a per-layer metric if and only if the cell's mix reports
what the metric moves; that is how every metric's ``workloads`` list is
made, so no list can name a cell that lacks the end-to-end metric.

``validate`` holds any manifest, derived or not, to the same rules;
``run.py`` calls it before every run.
"""
from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PATHS = [os.path.basename(HERE)]
E2E_FILE = "end_to_end.json"

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _load(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _names(sub: str) -> list:
    return sorted(f[:-5] for f in os.listdir(os.path.join(HERE, sub))
                  if f.endswith(".json"))


def derive() -> dict:
    e2e_file = _load("metrics", E2E_FILE)
    cells = {name: _load("cells", name + ".json")
             for name in _names("cells")}
    mixes = {c["traffic"]: _load("traffic", c["traffic"] + ".json")
             for c in cells.values()}

    def reporting(metric: str) -> list:
        return [name for name, c in cells.items()
                if metric == "setup_s"
                or metric in mixes[c["traffic"]]["reports"]]

    configs = []
    for cname in sorted({c["config"] for c in cells.values()}):
        cfg = _load("configs", cname + ".json")
        configs.append({
            "name": cname, "source": cfg["source"],
            "file": f"{PATHS[0]}/configs/{cname}.json",
            "reduced": sorted(cfg["reduced"]), "why": cfg["why"]})
    workloads = [{"name": name, "config": c["config"],
                  "traffic": c["traffic"], "chips": c["chips"],
                  "why": c["why"]} for name, c in cells.items()]
    # a metric that no cell reports yet stays out of the manifest: its
    # file waits for the PR that brings such a cell
    end_to_end = []
    for m in e2e_file["end_to_end"]:
        entry = dict(m)
        cells_of = reporting(m["name"])
        if not cells_of:
            continue
        if len(cells_of) != len(cells):
            entry["workloads"] = cells_of
        end_to_end.append(entry)
    per_layer = []
    for name in _names("metrics"):
        if name + ".json" == E2E_FILE:
            continue
        m = _load("metrics", name + ".json")
        if not reporting(m["moves"]):
            continue
        per_layer.append({
            "name": name, "unit": m["unit"], "better": m["better"],
            "source": m["source"], "layer": m["layer"], "moves": m["moves"],
            "workloads": reporting(m["moves"])})
    return {"command": ["python3", f"{PATHS[0]}/run.py"], "paths": PATHS,
            "run_seconds": e2e_file["run_seconds"], "configs": configs,
            "workloads": workloads, "end_to_end": end_to_end,
            "per_layer": per_layer}


def cells_reporting(manifest: dict, e2e_name: str) -> set:
    """The cells of a manifest that report one end-to-end metric."""
    for m in manifest["end_to_end"]:
        if m["name"] == e2e_name:
            return set(m.get("workloads",
                             [w["name"] for w in manifest["workloads"]]))
    return set()


def validate(manifest: dict) -> None:
    """Raise ManifestError on the first rule a manifest breaks."""
    def bad(msg: str):
        raise ManifestError(msg)

    cells = [w["name"] for w in manifest["workloads"]]
    e2e = [m["name"] for m in manifest["end_to_end"]]
    for kind, names in (("workload", cells), ("end_to_end", e2e),
                        ("per_layer", [m["name"] for m in
                                       manifest["per_layer"]]),
                        ("config", [c["name"] for c in
                                    manifest["configs"]])):
        for n in names:
            if not _NAME.match(n):
                bad(f"{kind} name {n!r} has a character or a length "
                    "that a name may not have")
        if len(set(names)) != len(names):
            bad(f"two {kind} entries share a name")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not _UNIT.match(m["unit"]):
            bad(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad(f"metric {m['name']}: better {m['better']!r}")
        if m["source"] not in _SOURCES:
            bad(f"metric {m['name']}: source {m['source']!r}")
        for w in m.get("workloads", ()):
            if w not in cells:
                bad(f"metric {m['name']} lists {w}, which is no cell")
    if "setup_s" not in e2e:
        bad("no setup_s among the end-to-end metrics")
    if len(e2e) - 1 > 4:
        bad("more than four end-to-end metrics besides setup_s")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad(f"end-to-end metric {m['name']}: source {m['source']!r}")
        if not 0.01 <= m["bound"] <= 0.25:
            bad(f"end-to-end metric {m['name']}: bound {m['bound']}")
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e:
            bad(f"per_layer metric {m['name']} moves {m['moves']}, "
                "which is no end-to-end metric")
        if "workloads" not in m:
            bad(f"per_layer metric {m['name']} has no workloads list")
        missing = set(m["workloads"]) - cells_reporting(manifest, m["moves"])
        for w in sorted(missing):
            bad(f"per_layer metric {m['name']} is reported on workload "
                f"{w}, where {m['moves']}, which it should move, is not")
    for w in manifest["workloads"]:
        if w["chips"] not in (1, 4):
            bad(f"cell {w['name']}: chips {w['chips']}")
        if len(w["why"]) > 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad(f"cell {w['name']}: why is over 200 characters or a line")
        reports = [n for n in e2e if w["name"] in
                   cells_reporting(manifest, n)]
        if "setup_s" not in reports or len(reports) < 2:
            bad(f"cell {w['name']} reports no end-to-end metric "
                "besides setup_s")
        if not any(w["name"] in m["workloads"]
                   for m in manifest["per_layer"]):
            bad(f"cell {w['name']} reports no per-layer metric")
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 2):
        bad(f"{four} of {len(cells)} cells ask for four chips")
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        if c["name"] not in used:
            bad(f"configuration {c['name']} has no cell")
        if len(c["source"]) > 200 or len(c["why"]) > 200:
            bad(f"configuration {c['name']}: source or why over 200")
        if len(c["reduced"]) > 16:
            bad(f"configuration {c['name']}: over 16 reduced keys")
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    if len(set(pairs)) != len(pairs):
        bad("a pair of configuration and traffic appears twice")
    if used - {c["name"] for c in manifest["configs"]}:
        bad("a cell names a configuration the manifest lacks")
    if not 1 <= manifest["run_seconds"] <= 51:
        bad(f"run_seconds {manifest['run_seconds']}")


def committed() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def main(argv) -> int:
    derived = derive()
    validate(derived)
    if "--write" in argv:
        with open(os.path.join(ROOT, "BENCHMARK.json"), "w") as f:
            json.dump(derived, f, indent=1)
            f.write("\n")
        return 0
    if "--check" in argv:
        if committed() != derived:
            print("manifest: BENCHMARK.json differs from what the files "
                  "under benchmarks/ derive; run --write", file=sys.stderr)
            return 1
        return 0
    print(json.dumps(derived, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
