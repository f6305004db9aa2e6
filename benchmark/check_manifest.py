"""Check BENCHMARK.json, and every file it points at, against the
driver's rules before a chip-second is spent.

    python3 benchmark/check_manifest.py          # exit 0 and `manifest ok`

PR 22 was refused before any run for a layer name with a space in it.
Every NAME (metric, cell, configuration, layer, traffic, `reduced` key)
is 1 to 64 characters from letters, digits, `_`, `.`, `-`, starting with
a letter, digit or `_`; a unit is 1 to 16 of those plus `/` and `%`.
"""

from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}\Z")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")
MAX_CELLS, MAX_RUN_SECONDS, CHECK_SECONDS = 24, 51, 43200


def line_ok(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 \
        and "\n" not in s and "\t" not in s


def check(manifest: dict, root: str = ROOT) -> list:
    errs = []

    def name(kind, s):
        if not isinstance(s, str) or not NAME.match(s):
            errs.append(f"{kind} {s!r}: a name is 1 to 64 characters from "
                        "letters, digits, '_', '.', '-', starting with a "
                        "letter, digit or '_'")

    if set(manifest) != TOP_KEYS:
        errs.append(f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
        return errs
    paths = manifest["paths"]
    if not (1 <= len(paths) <= 16) or not all(
            isinstance(p, str) and PATH.match(p) and not p.startswith("/")
            and ".." not in p.split("/") for p in paths):
        errs.append(f"paths {paths!r}: 1 to 16 relative directories")
    cmd = manifest["command"]
    if not (1 <= len(cmd) <= 32) or not all(line_ok(w) for w in cmd):
        errs.append("command: 1 to 32 words of 1 to 200 characters")
    for w in cmd:
        if w.startswith("/") or ".." in w.split("/"):
            errs.append(f"command word {w!r} leaves the repo")
        if os.path.exists(os.path.join(root, w)) and not any(
                w == p or w.startswith(p.rstrip("/") + "/") for p in paths):
            errs.append(f"command names {w!r}, a file outside paths")
    rs = manifest["run_seconds"]
    if not isinstance(rs, int) or not 1 <= rs <= MAX_RUN_SECONDS:
        errs.append(f"run_seconds {rs!r}: a whole number from 1 to 51")
    else:
        full = (2 + 14 * MAX_CELLS) * (rs + 60) + MAX_CELLS * 180 + 1200
        if full > CHECK_SECONDS:
            errs.append(f"run_seconds {rs}: a full check of {MAX_CELLS} "
                        f"cells takes {full}s > {CHECK_SECONDS}s")

    def under_paths(f):
        return any(f.startswith(p.rstrip("/") + "/") for p in paths)

    for group, keys in KEYS.items():
        seen = set()
        for e in manifest[group]:
            extra = set(e) - keys - ({"workloads"} if group in (
                "end_to_end", "per_layer") else set())
            if extra or keys - set(e):
                errs.append(f"{group} {e.get('name')!r}: keys "
                            f"{sorted(e)} != {sorted(keys)}")
                continue
            name(f"{group} name", e["name"])
            if e["name"] in seen:
                errs.append(f"{group}: two entries named {e['name']!r}")
            seen.add(e["name"])

    configs = {c["name"]: c for c in manifest["configs"]}
    files = set()
    for c in manifest["configs"]:
        if not line_ok(c.get("source")) or not line_ok(c.get("why")):
            errs.append(f"config {c['name']!r}: source and why are one "
                        "line of 1 to 200 characters")
        f = c.get("file", "")
        if not PATH.match(f) or not under_paths(f) or f in files:
            errs.append(f"config {c['name']!r}: file {f!r} must lie under "
                        "paths and be no other configuration's")
        files.add(f)
        if len(c.get("reduced", [])) > 16:
            errs.append(f"config {c['name']!r}: more than 16 reduced keys")
        for k in c.get("reduced", []):
            name(f"config {c['name']!r} reduced key", k)
        full_path = os.path.join(root, f)
        if not os.path.isfile(full_path):
            errs.append(f"config {c['name']!r}: no file {f}")
            continue
        with open(full_path) as fh:
            body = json.load(fh)
        if not line_ok(body.get("source")) or body["source"] != c["source"]:
            errs.append(f"config {c['name']!r}: the file's source must be "
                        "the manifest's, at most 200 characters")
        if set(body.get("reduced", {})) != set(c["reduced"]):
            errs.append(f"config {c['name']!r}: the file's reduced keys "
                        f"{sorted(body.get('reduced', {}))} != the "
                        f"manifest's {sorted(c['reduced'])}")
        for k in ("assumed", "precision", "memory", "schema"):
            if k not in body:
                errs.append(f"config {c['name']!r}: the file lacks {k!r}")

    cells = {}
    pairs = set()
    for w in manifest["workloads"]:
        name("cell config", w.get("config"))
        name("cell traffic", w.get("traffic"))
        if w.get("config") not in configs:
            errs.append(f"cell {w['name']!r}: no configuration "
                        f"{w.get('config')!r}")
        if w.get("chips") not in (1, 4):
            errs.append(f"cell {w['name']!r}: chips is 1 or 4")
        if not line_ok(w.get("why")):
            errs.append(f"cell {w['name']!r}: why is one line of 1 to 200 "
                        "characters")
        if (w.get("config"), w.get("traffic")) in pairs:
            errs.append(f"cell {w['name']!r}: its configuration and "
                        "traffic appear twice")
        pairs.add((w.get("config"), w.get("traffic")))
        tdir = os.path.join(root, paths[0], "traffic")
        found = [ext for ext in TRAFFIC_EXT if os.path.isfile(
            os.path.join(tdir, str(w.get("traffic")) + ext))]
        if not found:
            errs.append(f"cell {w['name']!r}: no traffic file "
                        f"{w.get('traffic')!r} in {tdir}")
        elif found[0] == ".json":
            with open(os.path.join(tdir, w["traffic"] + ".json")) as fh:
                drv = json.load(fh).get("driver", "")
            if not os.path.isfile(os.path.join(
                    root, paths[0], "drivers", drv + ".py")):
                errs.append(f"traffic {w['traffic']!r}: no driver {drv!r}")
        cells[w["name"]] = w
    if not 1 <= len(cells) <= MAX_CELLS:
        errs.append("1 to 24 cells")
    four = sum(w.get("chips") == 4 for w in manifest["workloads"])
    if four > max(1, len(cells) // 4):
        errs.append(f"{four} four-chip cells of {len(cells)}: at most 25 %")
    for cname in configs:
        if not any(w.get("config") == cname for w in manifest["workloads"]):
            errs.append(f"config {cname!r} is used by no cell")

    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        errs.append("end_to_end lacks setup_s")

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(str(m.get("unit"))):
            errs.append(f"metric {m['name']!r}: unit {m.get('unit')!r} is "
                        "1 to 16 letters, digits, '_', '/', '%', '.', '-'")
        if m.get("better") not in ("lower", "higher"):
            errs.append(f"metric {m['name']!r}: better is lower or higher")
        if m.get("source") not in SOURCES:
            errs.append(f"metric {m['name']!r}: source {m.get('source')!r}")
        for c in m.get("workloads", []):
            if c not in cells:
                errs.append(f"metric {m['name']!r}: no cell {c!r}")
    for m in manifest["end_to_end"]:
        if m.get("source") not in ("host_clock", "device_trace"):
            errs.append(f"end-to-end {m['name']!r}: source is host_clock "
                        "or device_trace")
        b = m.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.1:
            errs.append(f"end-to-end {m['name']!r}: bound {b!r} is from "
                        "0.01 to 0.1")
    for m in manifest["per_layer"]:
        name(f"per_layer metric {m['name']!r} layer", m.get("layer"))
        if "bound" in m:
            errs.append(f"per_layer {m['name']!r} has a bound")
        if m.get("moves") not in e2e or m.get("moves") == "setup_s":
            errs.append(f"per_layer {m['name']!r}: moves {m.get('moves')!r} "
                        "is not an end-to-end metric")
            continue
        for c in m.get("workloads", cells):
            if not reports(e2e[m["moves"]], c):
                errs.append(f"per_layer {m['name']!r}: cell {c!r} does not "
                            f"report {m['moves']!r}")
        if not os.path.isfile(os.path.join(
                root, paths[0], "layer_metrics", m["name"] + ".py")):
            errs.append(f"per_layer {m['name']!r}: no reader "
                        f"layer_metrics/{m['name']}.py")
    for cname in cells:
        if sum(reports(m, cname) for m in manifest["end_to_end"]) < 2:
            errs.append(f"cell {cname!r} reports no end-to-end metric "
                        "besides setup_s")
        if not any(reports(m, cname) for m in manifest["per_layer"]):
            errs.append(f"cell {cname!r} reports no per-layer metric")
    return errs


def main() -> int:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if os.path.getsize(path) > 64 * 1024:
        print("BENCHMARK.json is over 64 KiB")
        return 1
    with open(path) as fh:
        errs = check(json.load(fh))
    for e in errs:
        print("manifest:", e)
    print("manifest ok" if not errs else f"{len(errs)} faults")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
