"""BENCHMARK.json and the data files it names. Everything that belongs to
one configuration, one traffic mix or one per-layer metric is a file found
by NAME; no registry lists them, so a later PR adds files and entries and
edits nothing that is here — but for its cell's name appended to the
``workloads`` lists it joins in BENCHMARK.json, which is why no file under
``benchmark/`` repeats such a list."""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_manifest(path: Path | None = None) -> dict:
    with open(path or ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"{what} {name!r} is not in BENCHMARK.json "
                   f"(has: {', '.join(e['name'] for e in entries)})")


def load_json(rel: str | Path) -> dict:
    with open(ROOT / rel) as f:
        return json.load(f)


def load_cell(manifest: dict, workload: str) -> dict:
    """One cell resolved: its entry, its configuration file (a voice
    configuration pulls in the decoder file it names, unchanged), its traffic
    file, and the metrics it reports."""
    cell = _by_name(manifest["workloads"], workload, "workload")
    cfg_entry = _by_name(manifest["configs"], cell["config"], "config")
    config = load_json(cfg_entry["file"])
    if "decoder_config" in config:
        config["decoder"] = load_json(f"benchmark/configs/{config['decoder_config']}.json")
    traffic = load_json(f"benchmark/traffic/{cell['traffic']}.json")
    return {"cell": cell, "config_entry": cfg_entry, "config": config, "traffic": traffic,
            "end_to_end": metrics_of(manifest, "end_to_end", workload),
            "per_layer": metrics_of(manifest, "per_layer", workload)}


def metrics_of(manifest: dict, kind: str, workload: str) -> list[dict]:
    return [m for m in manifest[kind]
            if "workloads" not in m or workload in m["workloads"]]


VARIANT_KEYS = {"reader", "args", "note"}


def load_layer_metric(name: str, cell: str | None = None) -> dict:
    """One per-layer metric resolved for ``cell``: ``layer_metrics/<name>.json``
    holds the entry's fields and the default ``reader`` / ``args``; where this
    cell reads the quantity otherwise, ``layer_metrics/<name>/<cell>.json``
    — found from the two names alone — holds its own ``reader``, ``args`` and
    ``note`` and nothing else (unit, ``better``, ``source``, ``layer`` and
    ``moves`` are the entry's, the same in every cell). The cells are the
    manifest's alone: ``workloads`` is the entry's list in BENCHMARK.json (a
    file names cells only while no manifest does: the held-back voice cell's)."""
    spec = load_json(f"benchmark/layer_metrics/{name}.json")
    if cell is not None and NAME_RE.match(cell) and \
            (ROOT / f"benchmark/layer_metrics/{name}/{cell}.json").is_file():
        own = load_json(f"benchmark/layer_metrics/{name}/{cell}.json")
        if not {"reader", "args"} <= set(own) <= VARIANT_KEYS:
            raise ValueError(f"layer_metrics/{name}/{cell}.json holds {sorted(own)}: a cell's own file "
                             f"holds reader and args, a note if it likes, and nothing else")
        spec.update(own)
    entry = next((m for m in load_manifest()["per_layer"] if m["name"] == name), None)
    if entry is not None and "workloads" in entry:
        spec["workloads"] = entry["workloads"]
    return spec


# what a module found by name owes the harness (README.md has the reference's
# protocol in full); ``load_code`` refuses a module that lacks any of it
OWES = {"builders": ("build",), "generators": ("warm", "run"), "readers": ("read",),
        "reference": ("SAMPLE", "TOLERANCE", "CONTROL", "logits")}


def load_code(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` (kind: builders, generators, readers,
    reference), by name."""
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    mod = importlib.import_module(f"benchmark.{kind}.{name}")
    lacks = [a for a in OWES[kind] if not hasattr(mod, a)]
    if lacks:
        raise AttributeError(f"benchmark/{kind}/{name}.py lacks {lacks}: a module of {kind!r} "
                             f"owes {list(OWES[kind])}")
    return mod


def references_of(config: dict) -> list:
    """The references a resolved configuration is compared with: the decoder
    file's that it pulls in first, then its own."""
    return [c.get("reference") for c in (config.get("decoder"), config) if c]


def code_problems(cell: dict) -> list[str]:
    """Every piece of code a resolved cell names — builder, references,
    generator, its per-layer metrics' readers — imports and has what its
    kind owes; ``run.py`` asks before it builds anything, so that a wrong
    name costs no set-up and no window."""
    from .refcheck import SAMPLERS

    config = cell["config"]
    named = [("builders", config.get("builder"), "builder")]
    named += [("reference", r, "reference") for r in references_of(config)]
    named.append(("generators", cell["traffic"].get("generator"), "generator"))
    bad: list[str] = []
    for m in cell["per_layer"]:
        try:
            named.append(("readers", load_layer_metric(m["name"], cell["cell"]["name"]).get("reader"),
                          f"reader of {m['name']}"))
        except (OSError, ValueError) as e:
            bad.append(f"per-layer metric {m['name']}: no readable layer_metrics file ({e})")
    for kind, name, what in named:
        try:
            mod = load_code(kind, name)
        except (ImportError, AttributeError, ValueError) as e:
            bad.append(f"{what} {name!r}: {type(e).__name__}: {e}")
            continue
        if kind == "reference" and mod.SAMPLE not in SAMPLERS:
            bad.append(f"{what} {name!r}: SAMPLE {mod.SAMPLE!r} is none of {sorted(SAMPLERS)}")
    return bad


def validate(manifest: dict) -> list[str]:
    """What the contract can be checked for without running anything."""
    bad: list[str] = []
    keys = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    if set(manifest) != keys:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(keys)}")
    names: set[str] = set()
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for e in manifest[kind]:
            if not NAME_RE.match(e["name"]):
                bad.append(f"{kind}: bad name {e['name']!r}")
            if e["name"] in seen:
                bad.append(f"{kind}: duplicate name {e['name']!r}")
            seen.add(e["name"])
        if kind in ("end_to_end", "per_layer"):
            if names & seen:
                bad.append(f"metric named twice: {sorted(names & seen)}")
            names |= seen
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"] for c in manifest["configs"]}
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        if not NAME_RE.match(w["traffic"]):
            bad.append(f"workload {w['name']}: bad traffic name")
        if w["chips"] not in (1, 4) or not 1 <= len(w["why"]) <= 200:
            bad.append(f"workload {w['name']}: chips or why out of range")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT_RE.match(m["unit"]) or m["better"] not in ("lower", "higher") \
                or m["source"] not in SOURCES:
            bad.append(f"metric {m['name']}: bad unit, better or source")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"metric {m['name']}: unknown workload {w}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']}: source {m['source']}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"end-to-end {m['name']}: bound {m['bound']}")
    for m in manifest["per_layer"]:
        if not m.get("workloads"):  # an entry without one would be owed by every cell a later PR adds
            bad.append(f"per-layer {m['name']}: no workloads list")
        moved = e2e.get(m["moves"])
        if moved is None:
            bad.append(f"per-layer {m['name']}: moves unknown metric {m['moves']}")
            continue
        for w in m.get("workloads", list(cells)):
            if "workloads" in moved and w not in moved["workloads"]:
                bad.append(f"per-layer {m['name']}: cell {w} does not report {m['moves']}")
    for w in cells:
        if len(metrics_of(manifest, "end_to_end", w)) < 2 or not metrics_of(manifest, "per_layer", w):
            bad.append(f"cell {w}: needs setup_s, another end-to-end metric and a per-layer metric")
    return bad
