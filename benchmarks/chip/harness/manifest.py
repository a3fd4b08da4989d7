"""``BENCHMARK.json``: its rules, and the files each name in it stands for.

Everything that belongs to one item sits in files of its own, found by
the item's name, so adding a cell, a configuration, a traffic mix or a
per-layer metric takes new files and a new entry only:

  configuration  the ``file`` its entry names (sizes, deployment, limits),
                 with its plain reference ``reference/<reference>.py``
  traffic mix    ``traffic/<traffic>.json``
  per-layer      ``metrics/<name>.py``, a ``read(ctx)`` that returns the
  metric         reading or None where it finds nothing to read
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Dict, List

from harness import BENCH_DIR, REPO_ROOT, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection|head|"
                   r"_dim$|_rank$|expansion|experts_per_tok|embed)")


def load(root: Path = REPO_ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _line(s, what, errs):
    if not isinstance(s, str) or not 1 <= len(s) <= 200 or "\n" in s \
            or "\t" in s:
        errs.append(f"{what}: 1 to 200 characters on one line, no tab")


def _metrics_of(m: dict, cell: str, kind: str) -> List[dict]:
    return [x for x in m[kind]
            if "workloads" not in x or cell in x["workloads"]]


def validate(m: dict, root: Path = REPO_ROOT) -> List[str]:
    """Every rule of the benchmark contract that can be checked from the
    files alone; an empty list when the manifest is sound."""
    errs: List[str] = []
    root = Path(root)
    if set(m) != TOP:
        return [f"top-level keys must be {sorted(TOP)}, got {sorted(m)}"]
    if not (isinstance(m["run_seconds"], int)
            and 1 <= m["run_seconds"] <= 51):
        errs.append("run_seconds: a whole number from 1 to 51")
    if not 1 <= len(m["paths"]) <= 16 or not all(
            PATH.match(p) and ".." not in p.split("/")
            and not p.startswith("/") for p in m["paths"]):
        errs.append("paths: 1 to 16 relative paths")
    if not 1 <= len(m["command"]) <= 32:
        errs.append("command: 1 to 32 words")
    for w in m["command"][1:]:
        _line(w, "command word", errs)
        if w.startswith("/") or ".." in w.split("/"):
            errs.append(f"command names a path outside the repo: {w}")
        elif "/" in w and not any(w.startswith(p.rstrip("/") + "/")
                                  for p in m["paths"]):
            errs.append(f"command names a file outside paths: {w}")
    names = set()
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for x in m[kind]:
            n = x.get("name", "")
            if not NAME.match(n):
                errs.append(f"{kind}: bad name {n!r}")
            key = "metric" if kind in ("end_to_end", "per_layer") else kind
            if (key, n) in names:
                errs.append(f"{kind}: duplicate name {n!r}")
            names.add((key, n))
    configs = {c["name"]: c for c in m["configs"]}
    if not 1 <= len(configs) <= 24:
        errs.append("configs: 1 to 24")
    for c in m["configs"]:
        if set(c) != CONFIG_KEYS:
            errs.append(f"config {c['name']}: keys must be "
                        f"{sorted(CONFIG_KEYS)}")
            continue
        _line(c["source"], f"config {c['name']} source", errs)
        _line(c["why"], f"config {c['name']} why", errs)
        if len(c["reduced"]) > 16 or not all(NAME.match(k)
                                             for k in c["reduced"]):
            errs.append(f"config {c['name']}: reduced, at most 16 names")
        for k in c["reduced"]:
            if WIDTH.search(k):
                errs.append(f"config {c['name']}: reduced names a width {k}")
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in m["paths"]):
            errs.append(f"config {c['name']}: file outside paths")
        if not (root / c["file"]).is_file():
            errs.append(f"config {c['name']}: no file {c['file']}")
    if len({c["file"] for c in m["configs"]}) != len(m["configs"]):
        errs.append("configs: each needs a file of its own")
    cells = m["workloads"]
    if not 1 <= len(cells) <= 24:
        errs.append("workloads: 1 to 24 cells")
    pairs = set()
    for w in cells:
        if set(w) != CELL_KEYS:
            errs.append(f"cell {w['name']}: keys must be {sorted(CELL_KEYS)}")
            continue
        _line(w["why"], f"cell {w['name']} why", errs)
        if w["config"] not in configs:
            errs.append(f"cell {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            errs.append(f"cell {w['name']}: chips must be 1 or 4")
        if not NAME.match(w["traffic"]) or not traffic_file(
                w["traffic"], root).is_file():
            errs.append(f"cell {w['name']}: no traffic file for "
                        f"{w['traffic']}")
        if (w["config"], w["traffic"]) in pairs:
            errs.append(f"cell {w['name']}: configuration and traffic "
                        f"pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    four = sum(w.get("chips") == 4 for w in cells)
    if four > max(1, len(cells) // 2):
        errs.append("at most half of the cells may ask for 4 chips")
    used = {w.get("config") for w in cells}
    for c in configs:
        if c not in used:
            errs.append(f"config {c}: no cell uses it")
    cell_names = {w["name"] for w in cells}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    if "setup_s" not in e2e:
        errs.append("end_to_end: setup_s is required")
    for x in m["end_to_end"]:
        if set(x) - {"workloads"} != E2E_KEYS:
            errs.append(f"metric {x['name']}: keys must be "
                        f"{sorted(E2E_KEYS)} (+ workloads)")
            continue
        if x["source"] not in ("host_clock", "device_trace"):
            errs.append(f"metric {x['name']}: end-to-end source")
        if not 0.01 <= x["bound"] <= 0.25:
            errs.append(f"metric {x['name']}: bound from 0.01 to 0.25")
    for x in m["per_layer"]:
        if set(x) - {"workloads"} != LAYER_KEYS:
            errs.append(f"metric {x['name']}: keys must be "
                        f"{sorted(LAYER_KEYS)} (+ workloads)")
            continue
        _line(x["layer"], f"metric {x['name']} layer", errs)
        if x["source"] not in ("device_trace", "program_span",
                               "program_counter", "host_clock"):
            errs.append(f"metric {x['name']}: unknown source")
        if x["moves"] not in e2e:
            errs.append(f"metric {x['name']}: moves {x['moves']}, which is "
                        f"no end-to-end metric")
        if not metric_file(x["name"], root).is_file():
            errs.append(f"metric {x['name']}: no reader "
                        f"{metric_file(x['name'], root)}")
    for x in m["end_to_end"] + m["per_layer"]:
        if not UNIT.match(x.get("unit", "")):
            errs.append(f"metric {x['name']}: bad unit {x.get('unit')!r}")
        if x.get("better") not in ("lower", "higher"):
            errs.append(f"metric {x['name']}: better is lower or higher")
        for c in x.get("workloads", []):
            if c not in cell_names:
                errs.append(f"metric {x['name']}: unknown cell {c}")
    for w in cells:
        got = {x["name"] for x in _metrics_of(m, w["name"], "end_to_end")}
        if "setup_s" not in got or len(got) < 2:
            errs.append(f"cell {w['name']}: reports setup_s and one more "
                        f"end-to-end metric")
        layer = _metrics_of(m, w["name"], "per_layer")
        if not layer:
            errs.append(f"cell {w['name']}: reports no per-layer metric")
        for x in layer:
            if x.get("moves") not in got:
                errs.append(f"cell {w['name']}: per-layer {x['name']} moves "
                            f"{x.get('moves')}, which the cell does not "
                            f"report")
    if len(json.dumps(m)) > 64 * 1024:
        errs.append("BENCHMARK.json is over 64 KiB")
    return errs


def traffic_file(name: str, root: Path = REPO_ROOT) -> Path:
    return Path(root) / BENCH_DIR.relative_to(REPO_ROOT) / "traffic" / (
        name + ".json")


def metric_file(name: str, root: Path = REPO_ROOT) -> Path:
    return Path(root) / BENCH_DIR.relative_to(REPO_ROOT) / "metrics" / (
        name + ".py")


def reference_file(name: str, root: Path = REPO_ROOT) -> Path:
    return Path(root) / BENCH_DIR.relative_to(REPO_ROOT) / "reference" / (
        name + ".py")


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file, as run
    mix: dict              # the traffic mix
    model: object          # the configuration's plain reference module
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, object]


def resolve(m: dict, workload: str, root: Path = REPO_ROOT) -> Cell:
    """The cell ``workload`` with every file it names loaded."""
    cells = {w["name"]: w for w in m["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    entry = {c["name"]: c for c in m["configs"]}[w["config"]]
    cfg = json.loads((Path(root) / entry["file"]).read_text())
    model = load_module(reference_file(cfg["reference"], root),
                        f"bench_reference_{cfg['reference']}")
    layer = _metrics_of(m, workload, "per_layer")
    readers = {x["name"]: load_module(metric_file(x["name"], root),
                                      f"bench_metric_{x['name']}")
               for x in layer}
    return Cell(workload, int(w["chips"]), cfg,
                traffic.load(traffic_file(w["traffic"], root)), model,
                _metrics_of(m, workload, "end_to_end"), layer, readers)
