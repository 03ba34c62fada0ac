"""BENCHMARK.json from the benchmark's data files.

    python3 benchmark/manifest.py --write     # after adding files
    python3 benchmark/manifest.py --check     # what the tests run

The manifest lists what the files say and nothing else: one entry per
``workloads/<cell>.json``, per configuration those cells use, and per
``metrics/<metric>.json`` that applies to at least one cell.  ``command``,
``paths`` and ``run_seconds`` are kept from the manifest that is there.
A later PR adds files and runs ``--write``; it edits no file.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DEFAULTS = {"command": ["python3", "benchmark/run.py"],
            "paths": ["benchmark"], "run_seconds": 10}


def build(old: dict) -> dict:
    from benchmark import cells
    loaded = [cells.load_cell(n) for n in cells.all_cells()]
    loaded.sort(key=lambda c: (c.get("order", 99), c["name"]))
    configs, seen = [], set()
    for c in loaded:
        if c["config"] in seen:
            continue
        seen.add(c["config"])
        cfg = cells.load_config(c["config"])
        configs.append({
            "name": cfg["name"], "source": cfg["source"],
            "file": f"benchmark/configs/{cfg['name']}.json",
            "reduced": sorted(cfg.get("reduced", {})),
            "why": cfg["why"]})
    workloads = [{"name": c["name"], "config": c["config"],
                  "traffic": c["traffic"], "chips": int(c["chips"]),
                  "why": c["why"]} for c in loaded]
    end_to_end, per_layer = [], []
    for path in sorted(glob.glob(os.path.join(HERE, "metrics", "*.json"))):
        with open(path) as f:
            m = json.load(f)
        on = [c["name"] for c in loaded if cells.metric_applies(m, c)]
        if not on:
            continue
        entry = {"name": m["name"], "unit": m["unit"],
                 "better": m["better"]}
        if m.get("end_to_end"):
            entry.update(bound=m["bound"], source=m["source"])
            target = end_to_end
        else:
            entry.update(source=m["source"], layer=m["layer"],
                         moves=m["moves"])
            target = per_layer
        if len(on) < len(loaded):
            entry["workloads"] = on
        target.append(entry)
    return {"command": old.get("command", DEFAULTS["command"]),
            "paths": old.get("paths", DEFAULTS["paths"]),
            "run_seconds": old.get("run_seconds", DEFAULTS["run_seconds"]),
            "configs": configs, "workloads": workloads,
            "end_to_end": end_to_end, "per_layer": per_layer}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    g = p.add_mutually_exclusive_group(required=True)
    g.add_argument("--write", action="store_true")
    g.add_argument("--check", action="store_true")
    args = p.parse_args(argv)
    path = os.path.join(ROOT, "BENCHMARK.json")
    old = {}
    if os.path.isfile(path):
        with open(path) as f:
            old = json.load(f)
    new = build(old)
    if args.write:
        with open(path, "w") as f:
            json.dump(new, f, indent=2)
            f.write("\n")
        print(f"wrote {path}: {len(new['workloads'])} cells, "
              f"{len(new['end_to_end'])} + {len(new['per_layer'])} metrics")
        return 0
    if new != old:
        print("BENCHMARK.json does not say what the files say; run "
              "python3 benchmark/manifest.py --write", file=sys.stderr)
        return 1
    print("BENCHMARK.json agrees with the files")
    return 0


if __name__ == "__main__":
    sys.exit(main())
