"""``BENCHMARK.json``: loading, lookup by name, and the naming rules."""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import List

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"device_trace", "host_clock"}


def load(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def workload(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def metrics_for(manifest: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics one cell reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell in m["workloads"]]


def problems(manifest: dict) -> List[str]:
    """Every breach of the naming, unit and source rules, as text."""
    out = []
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest.get(key, []):
            names.append((key, entry.get("name", "")))
    for key, name in names:
        if not NAME_RE.match(name):
            out.append(f"{key}: bad name {name!r}")
    for key in ("configs", "workloads"):
        seen = [e["name"] for e in manifest.get(key, [])]
        if len(seen) != len(set(seen)):
            out.append(f"{key}: duplicate names")
    metric_names = [m["name"] for k in ("end_to_end", "per_layer")
                    for m in manifest.get(k, [])]
    if len(metric_names) != len(set(metric_names)):
        out.append("metrics: duplicate names")
    for w in manifest.get("workloads", []):
        for k in ("config", "traffic"):
            if not NAME_RE.match(w.get(k, "")):
                out.append(f"workload {w['name']}: bad {k}")
        if w.get("chips") not in (1, 4):
            out.append(f"workload {w['name']}: chips must be 1 or 4")
    for c in manifest.get("configs", []):
        for r in c.get("reduced", []):
            if not NAME_RE.match(r):
                out.append(f"config {c['name']}: bad reduced key {r!r}")
    e2e = {m["name"] for m in manifest.get("end_to_end", [])}
    for kind in ("end_to_end", "per_layer"):
        for m in manifest.get(kind, []):
            if not UNIT_RE.match(m.get("unit", "")):
                out.append(f"{m['name']}: bad unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                out.append(f"{m['name']}: better must be lower or higher")
            allowed = E2E_SOURCES if kind == "end_to_end" else SOURCES
            if m.get("source") not in allowed:
                out.append(f"{m['name']}: bad source {m.get('source')!r}")
            if kind == "per_layer" and m.get("moves") not in e2e:
                out.append(f"{m['name']}: moves {m.get('moves')!r} is not "
                           "an end-to-end metric")
    if "setup_s" not in e2e:
        out.append("end_to_end: setup_s is missing")
    return out
