#!/usr/bin/env python3
"""Check BENCHMARK.json against the contract it is sent under, and
against the files this directory holds, before a chip-minute is spent:

    python3 perfbench/manifest.py

Exit 0 and `manifest ok`, or every fault found, one a line.
"""
from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                   r"head_size|expansion|experts_per_tok)")
MAX_RUNS_SECONDS = 43200


def line(text, what: str, faults: list) -> None:
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        faults.append(f"{what}: not 1 to 200 characters on one line")


def check(manifest: dict, root: str = ROOT) -> list:
    faults: list = []
    if set(manifest) != KEYS:
        faults.append(f"top-level keys {sorted(set(manifest) ^ KEYS)} "
                      "missing or not allowed")
        return faults
    paths = manifest["paths"]
    if not (1 <= len(paths) <= 16):
        faults.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            faults.append(f"path {p!r}: not a relative path of the "
                          "allowed characters")

    def under_paths(f: str) -> bool:
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in paths)
    cmd = manifest["command"]
    if not (1 <= len(cmd) <= 32):
        faults.append("command: 1 to 32 strings")
    for word in cmd:
        line(word, f"command word {word!r}", faults)
        if word.startswith("/") or ".." in word.split("/"):
            faults.append(f"command word {word!r} leaves the repo")
        if "/" in word and os.path.exists(os.path.join(root, word)) \
                and not under_paths(word):
            faults.append(f"command names {word!r}, outside paths")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        faults.append("run_seconds: a whole number from 1 to 51")
    elif (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 > MAX_RUNS_SECONDS:
        faults.append(f"run_seconds {rs}: a full check of 24 cells does "
                      f"not fit {MAX_RUNS_SECONDS} s")

    names: set = set()

    def fresh(kind: str, name) -> None:
        if not (isinstance(name, str) and NAME.match(name)):
            faults.append(f"{kind} name {name!r}: not a name")
        if (kind, name) in names:
            faults.append(f"{kind} name {name!r} twice")
        names.add((kind, name))

    configs = {}
    if not (1 <= len(manifest["configs"]) <= 24):
        faults.append("configs: 1 to 24")
    files = set()
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            faults.append(f"config {c.get('name')!r}: keys {sorted(c)}")
            continue
        fresh("config", c["name"])
        configs[c["name"]] = c
        line(c["source"], f"config {c['name']} source", faults)
        line(c["why"], f"config {c['name']} why", faults)
        if not under_paths(c["file"]) or not PATH.match(c["file"]):
            faults.append(f"config {c['name']}: file {c['file']!r} is not "
                          "under paths")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            faults.append(f"config {c['name']}: no file {c['file']}")
        if c["file"] in files:
            faults.append(f"config file {c['file']} used twice")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            faults.append(f"config {c['name']}: over 16 reduced keys")
        for key in c["reduced"]:
            if not NAME.match(key):
                faults.append(f"config {c['name']}: reduced key {key!r}")
            if WIDTH.search(key):
                faults.append(f"config {c['name']}: reduced names the "
                              f"width {key!r}")

    cells = {}
    pairs = set()
    if not (1 <= len(manifest["workloads"]) <= 24):
        faults.append("workloads: 1 to 24")
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            faults.append(f"workload {w.get('name')!r}: keys {sorted(w)}")
            continue
        fresh("workload", w["name"])
        cells[w["name"]] = w
        line(w["why"], f"workload {w['name']} why", faults)
        if w["config"] not in configs:
            faults.append(f"workload {w['name']}: no config {w['config']!r}")
        if not NAME.match(w["traffic"]):
            faults.append(f"workload {w['name']}: traffic {w['traffic']!r}")
        elif not os.path.isfile(os.path.join(
                HERE, "traffic", f"{w['traffic']}.json")):
            faults.append(f"workload {w['name']}: no traffic file "
                          f"perfbench/traffic/{w['traffic']}.json")
        if (w["config"], w["traffic"]) in pairs:
            faults.append(f"workload {w['name']}: that configuration and "
                          "traffic appear twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            faults.append(f"workload {w['name']}: chips {w['chips']!r}")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 2):
        faults.append(f"{four} of {len(cells)} cells ask for four chips")
    for name, c in configs.items():
        if not any(w["config"] == name for w in cells.values()):
            faults.append(f"config {name}: no cell uses it")

    def metric(m: dict, kind: str, keys: set) -> list:
        """The cells a metric is reported in."""
        allowed = keys | {"workloads"}
        if not (keys <= set(m) <= allowed):
            faults.append(f"{kind} metric {m.get('name')!r}: keys "
                          f"{sorted(set(m) ^ keys)}")
            return []
        fresh("metric", m["name"])
        if not UNIT.match(m["unit"]):
            faults.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            faults.append(f"metric {m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            faults.append(f"metric {m['name']}: source {m['source']!r}")
        listed = m.get("workloads")
        if listed is None:
            return list(cells)
        for w in listed:
            if w not in cells:
                faults.append(f"metric {m['name']}: no workload {w!r}")
        if not listed:
            faults.append(f"metric {m['name']}: an empty workloads list")
        return [w for w in listed if w in cells]

    e2e_cells = {}
    if not (1 <= len(manifest["end_to_end"]) <= 16):
        faults.append("end_to_end: 1 to 16 metrics")
    for m in manifest["end_to_end"]:
        where = metric(m, "end_to_end",
                       {"name", "unit", "better", "bound", "source"})
        if "name" not in m:
            continue
        e2e_cells[m["name"]] = set(where)
        if m.get("source") not in ("host_clock", "device_trace"):
            faults.append(f"end-to-end metric {m['name']}: source "
                          f"{m.get('source')!r}")
        if not os.path.isfile(os.path.join(
                HERE, "end_to_end", f"{m['name']}.json")):
            faults.append(f"end-to-end metric {m['name']}: no file "
                          f"perfbench/end_to_end/{m['name']}.json")
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25):
            faults.append(f"end-to-end metric {m['name']}: bound {b!r} "
                          "outside 0.01 to 0.25")
    if "setup_s" not in e2e_cells:
        faults.append("no end-to-end metric setup_s")
    elif e2e_cells["setup_s"] != set(cells):
        faults.append("setup_s is not reported in every cell")

    layer_cells = {w: 0 for w in cells}
    if not (1 <= len(manifest["per_layer"]) <= 128):
        faults.append("per_layer: 1 to 128 metrics")
    for m in manifest["per_layer"]:
        where = metric(m, "per_layer", {"name", "unit", "better", "source",
                                        "layer", "moves"})
        if "name" not in m:
            continue
        line(m.get("layer"), f"metric {m['name']} layer", faults)
        moves = m.get("moves")
        if moves not in e2e_cells:
            faults.append(f"per_layer metric {m['name']} moves {moves!r}, "
                          "which is no end-to-end metric")
            continue
        for w in where:
            layer_cells[w] += 1
            # the rule that refused PR 22
            if w not in e2e_cells[moves]:
                faults.append(
                    f"per_layer metric {m['name']} is reported on workload "
                    f"{w}, where {moves}, which it should move, is not")
        if not os.path.isfile(os.path.join(
                HERE, "layer_metrics", f"{m['name']}.json")):
            faults.append(f"per_layer metric {m['name']}: no file "
                          f"perfbench/layer_metrics/{m['name']}.json")
        if re.search(r"roofline|mfu", m["name"]) and m["unit"] != "%":
            faults.append(f"metric {m['name']}: a share has the unit %")
    for w in cells:
        others = [n for n, ws in e2e_cells.items()
                  if n != "setup_s" and w in ws]
        if not others:
            faults.append(f"workload {w}: no end-to-end metric besides "
                          "setup_s")
        if not layer_cells[w]:
            faults.append(f"workload {w}: no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        faults.append("the file is over 64 KiB")
    return faults


def main() -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    faults = check(manifest)
    for fault in faults:
        print(fault)
    if not faults:
        print("manifest ok")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
