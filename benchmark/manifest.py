"""``run.py --check-manifest``: BENCHMARK.json and the data files held
to the contract's letter before any chip time is spent on them. Two
earlier attempts were lost to a string here; every string is checked
(``isascii() and isprintable()``), every length, every name, and every
reference from a cell or a metric to something that has to exist.
"""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
DATA_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")
SOURCES = ("device_trace", "program_span", "program_counter",
           "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer",
                  "moves"},
}
MAX_COUNT = {"configs": 24, "workloads": 24, "end_to_end": 16,
             "per_layer": 128}


def _strings(node, where, out):
    if isinstance(node, str):
        out.append((where, node))
    elif isinstance(node, dict):
        for k, v in node.items():
            out.append((f"{where} key", k))
            _strings(v, f"{where}.{k}", out)
    elif isinstance(node, list):
        for i, v in enumerate(node):
            _strings(v, f"{where}[{i}]", out)


def check(root: str) -> list:
    err = []
    path = os.path.join(root, "BENCHMARK.json")
    if os.path.getsize(path) > 64 << 10:
        err.append("BENCHMARK.json is over 64 KiB")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    if set(bench) != TOP_KEYS:
        err.append(f"top-level keys {sorted(set(bench) ^ TOP_KEYS)}")
        return err
    found: list = []
    _strings(bench, "BENCHMARK.json", found)

    def line(where, s, limit=200):
        if not (s.isascii() and s.isprintable()
                and 1 <= len(s) <= limit):
            err.append(f"{where}: {s!r} is not 1 to {limit} printable "
                       "ASCII characters on one line")

    for where, s in found:
        line(where, s)

    def name(where, s):
        if not isinstance(s, str) or not NAME.match(s):
            err.append(f"{where}: {s!r} is not a name")

    # command and paths
    if not (1 <= len(bench["command"]) <= 32):
        err.append("command: 1 to 32 words")
    paths = bench["paths"]
    if not (1 <= len(paths) <= 16):
        err.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p:
            err.append(f"paths: {p!r}")
    for word in bench["command"]:
        if word.startswith("/") or ".." in word.split("/"):
            err.append(f"command: {word!r} leads out of the repo")
        if os.path.exists(os.path.join(root, word)) and not any(
                word == p or word.startswith(p + "/") for p in paths):
            err.append(f"command: {word!r} is outside paths")
    rs = bench["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        err.append(f"run_seconds {rs!r}: a whole number, 1 to 51")

    # entries: keys, counts, names
    for section, keys in KEYS.items():
        entries = bench[section]
        if not (1 <= len(entries) <= MAX_COUNT[section]):
            err.append(f"{section}: 1 to {MAX_COUNT[section]} entries")
        seen = set()
        for e in entries:
            extra = set(e) - keys - (
                {"workloads"} if section in ("end_to_end",
                                             "per_layer") else set())
            if extra or keys - set(e):
                err.append(f"{section} {e.get('name')}: keys "
                           f"{sorted(extra | (keys - set(e)))}")
            name(f"{section} name", e.get("name"))
            if e.get("name") in seen:
                err.append(f"{section}: {e.get('name')} twice")
            seen.add(e.get("name"))
    metric_names = [m["name"] for m in bench["end_to_end"]
                    + bench["per_layer"]]
    if len(set(metric_names)) != len(metric_names):
        err.append("a metric name appears twice")

    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        f = c["file"]
        if not any(f.startswith(p + "/") for p in paths):
            err.append(f"config {c['name']}: file outside paths")
        if f in files:
            err.append(f"config file {f} used twice")
        files.add(f)
        if not (isinstance(c["reduced"], list)
                and len(c["reduced"]) <= 16):
            err.append(f"config {c['name']}: reduced")
        for k in c["reduced"]:
            name(f"config {c['name']} reduced", k)
        if c["name"] not in {w["config"] for w in bench["workloads"]}:
            err.append(f"config {c['name']}: no cell uses it")
        try:
            with open(os.path.join(root, f), encoding="utf-8") as fh:
                doc = json.load(fh)
        except (OSError, ValueError) as e:
            err.append(f"config {c['name']}: {f}: {e}")
            continue
        inner: list = []
        _strings(doc, f, inner)
        for where, s in inner:
            line(where, s, 400)
        if doc.get("source") != c["source"]:
            err.append(f"config {c['name']}: source differs from "
                       "its file's")
        for k in c["reduced"]:
            if k not in doc.get("reduced", {}):
                err.append(f"config {c['name']}: reduced key {k} "
                           "is not explained in its file")
    pairs = set()
    four = 0
    for w in bench["workloads"]:
        if w["config"] not in configs:
            err.append(f"cell {w['name']}: config {w['config']}")
        name(f"cell {w['name']} traffic", w["traffic"])
        if w["chips"] not in (1, 4):
            err.append(f"cell {w['name']}: chips {w['chips']}")
        four += w["chips"] == 4
        if (w["config"], w["traffic"]) in pairs:
            err.append(f"cell {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        traffic = [os.path.join(root, p, "workloads",
                                w["traffic"] + ext)
                   for p in paths for ext in DATA_EXT]
        there = [t for t in traffic if os.path.exists(t)]
        if not there:
            err.append(f"cell {w['name']}: no traffic file "
                       f"workloads/{w['traffic']}.json")
        elif there[0].endswith(".json"):
            with open(there[0], encoding="utf-8") as fh:
                mode = json.load(fh).get("mode")
            if not os.path.exists(os.path.join(
                    os.path.dirname(os.path.dirname(there[0])),
                    "modes", f"{mode}.py")):
                err.append(f"cell {w['name']}: no traffic mode "
                           f"modes/{mode}.py")
    if four > max(1, len(cells) // 2):
        err.append("more than half of the cells ask for 4 chips")

    def listed(m):
        for w in m.get("workloads", []):
            if w not in cells:
                err.append(f"metric {m['name']}: workload {w}")
        return set(m.get("workloads", cells))

    def metric_file(m, directory, apart=()):
        """A metric's own file says what BENCHMARK.json says of it
        (but for the keys ``apart``, which only the manifest holds)
        and names a reader that exists."""
        mf = os.path.join(root, paths[0], directory,
                          m["name"] + ".json")
        try:
            with open(mf, encoding="utf-8") as fh:
                doc = json.load(fh)
        except (OSError, ValueError) as e:
            err.append(f"metric {m['name']}: {e}")
            return
        for k, v in m.items():
            if k not in apart and doc.get(k) != v:
                err.append(f"metric {m['name']}: {k} differs from "
                           "its file's")
        reader = os.path.join(root, paths[0], "readers",
                              str(doc.get("reader")) + ".py")
        if not os.path.exists(reader):
            err.append(f"metric {m['name']}: no reader "
                       f"{doc.get('reader')}")

    e2e = {m["name"]: listed(m) for m in bench["end_to_end"]}
    if "setup_s" not in e2e or e2e.get("setup_s") != set(cells):
        err.append("setup_s has to be an end-to-end metric of "
                   "every cell")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT.match(m.get("unit", "")):
            err.append(f"metric {m['name']}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            err.append(f"metric {m['name']}: better")
        if m.get("source") not in SOURCES:
            err.append(f"metric {m['name']}: source")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            err.append(f"metric {m['name']}: an end-to-end metric "
                       "is the harness's own: host_clock or "
                       "device_trace")
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25):
            err.append(f"metric {m['name']}: bound {b!r}")
    layer_cells = set()
    for m in bench["per_layer"]:
        line(f"metric {m['name']} layer", m["layer"])
        if m["moves"] not in e2e:
            err.append(f"metric {m['name']}: moves {m['moves']}")
            continue
        # without the key: every cell that reports what it moves
        cells_of = listed(m) if "workloads" in m else e2e[m["moves"]]
        layer_cells |= cells_of
        if not cells_of <= e2e[m["moves"]]:
            err.append(f"metric {m['name']}: a listed cell does not "
                       f"report {m['moves']}")
        if ("roofline" in m["name"] or "mfu" in m["name"]) \
                and m["unit"] != "%":
            err.append(f"metric {m['name']}: a share's unit is %")
        metric_file(m, "layer_metrics")
    for m in bench["end_to_end"]:
        metric_file(m, "end_to_end", ("bound", "workloads"))
    for c in cells:
        if not any(c in ws and n != "setup_s"
                   for n, ws in e2e.items()):
            err.append(f"cell {c}: no end-to-end metric but setup_s")
        if c not in layer_cells:
            err.append(f"cell {c}: no per-layer metric")
    return err
