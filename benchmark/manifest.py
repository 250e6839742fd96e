"""``BENCHMARK.json`` and the data files its names resolve to.

A cell names a configuration, a traffic mix and (through each metric's
optional ``workloads`` list) the metrics it reports. Every name is the
name of a file; one that cannot be found is an error, never a default.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _reported_by(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def traffic_path(traffic: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", traffic + ".json")


def layer_metric_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "layer_metrics", name + ".json")


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict       # the configuration's file, as run
    traffic: dict      # the traffic mix's file
    end_to_end: tuple  # manifest entries this cell reports
    per_layer: tuple   # (manifest entry, reader spec from layer_metrics/)


def load_cell(manifest: dict, name: str, root: str = ROOT) -> Cell:
    """Resolve cell ``name`` to its files; raises ``ManifestError`` on
    any name that has no file."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json "
                            f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if cell["config"] not in configs:
        raise ManifestError(f"workload {name!r}: no config "
                            f"{cell['config']!r} in BENCHMARK.json")
    config = _load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = _load_json(traffic_path(cell["traffic"]))
    per_layer = tuple(
        (m, _load_json(layer_metric_path(m["name"])))
        for m in manifest["per_layer"] if _reported_by(m, name))
    end_to_end = tuple(m for m in manifest["end_to_end"]
                       if _reported_by(m, name))
    return Cell(name, int(cell["chips"]), config, traffic, end_to_end,
                per_layer)


def problems(manifest: dict, root: str = ROOT) -> list:
    """Everything wrong with the manifest that can be seen without a
    run, as sentences; empty when it is well-formed."""
    out: list = []
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[section]]
        for n in names:
            if not NAME_RE.match(n):
                out.append(f"{section}: name {n!r} outside the allowed "
                           "characters")
        if len(set(names)) != len(names):
            out.append(f"{section}: a name appears twice")
    if set(e2e) & {m["name"] for m in manifest["per_layer"]}:
        out.append("a metric is both end-to-end and per-layer")
    for m in [*manifest["end_to_end"], *manifest["per_layer"]]:
        if not UNIT_RE.match(m["unit"]):
            out.append(f"metric {m['name']}: unit {m['unit']!r} not allowed")
        if m["better"] not in ("lower", "higher"):
            out.append(f"metric {m['name']}: better={m['better']!r}")
        if m["source"] not in SOURCES:
            out.append(f"metric {m['name']}: source {m['source']!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                out.append(f"metric {m['name']}: unknown workload {w!r}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"end-to-end {m['name']}: source {m['source']!r}")
        if not 0.01 <= m["bound"] <= 0.25:
            out.append(f"end-to-end {m['name']}: bound {m['bound']}")
    if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
        out.append("setup_s must be an end-to-end metric of every cell")
    for c in manifest["configs"]:
        if not os.path.isfile(os.path.join(root, c["file"])):
            out.append(f"config {c['name']}: no file {c['file']}")
        if not any(w["config"] == c["name"] for w in manifest["workloads"]):
            out.append(f"config {c['name']}: used by no cell")
    pairs = set()
    for w in manifest["workloads"]:
        for key in ("config", "traffic"):
            if not NAME_RE.match(w[key]):
                out.append(f"workload {w['name']}: {key} {w[key]!r}")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"workload {w['name']}: config and traffic repeat")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            out.append(f"workload {w['name']}: why is not one line of "
                       "at most 200 characters")
        try:
            cell = load_cell(manifest, w["name"], root)
        except ManifestError as e:
            out.append(str(e))
            continue
        mine = {m["name"] for m in cell.end_to_end}
        if len(mine - {"setup_s"}) < 1 or not cell.per_layer:
            out.append(f"workload {w['name']}: needs setup_s, another "
                       "end-to-end metric and a per-layer metric")
        for m, spec in cell.per_layer:
            if m["moves"] not in mine:
                out.append(f"per-layer {m['name']} moves {m['moves']!r}, "
                           f"which cell {w['name']} does not report")
            for key in ("layer", "unit", "moves"):
                if spec.get(key) != m[key]:
                    out.append(f"per-layer {m['name']}: {key} differs "
                               "between BENCHMARK.json and its file")
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    if four > max(1, len(cells) // 2):
        out.append(f"{four} of {len(cells)} cells ask for 4 chips")
    return out
