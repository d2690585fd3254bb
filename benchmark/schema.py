"""Checks of ``BENCHMARK.json``: the contract's limits, before any run.

``run.py`` calls :func:`check` at start and a test calls it too. It raises
:class:`SchemaError` naming the first breach.
"""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
# Words that mark a width, which ``reduced`` may never name.
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head_dim", "head_size",
               "expand", "expansion", "experts_per_tok")
MAX_BYTES = 64 * 1024
MAX_RUN_SECONDS = 51


class SchemaError(ValueError):
    pass


def _need(ok: bool, message: str) -> None:
    if not ok:
        raise SchemaError(message)


def _line(text, what: str) -> None:
    _need(
        isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text,
        f"{what}: 1 to 200 characters on one line with no tab, got {text!r}",
    )


def _name(text, what: str) -> None:
    _need(isinstance(text, str) and NAME.match(text) is not None, f"{what}: not a permitted name: {text!r}")


def _keys(entry: dict, required: set, optional: set, what: str) -> None:
    keys = set(entry)
    _need(required <= keys and keys <= required | optional,
          f"{what}: keys {sorted(keys)}, wanted {sorted(required)} (+ {sorted(optional)})")


def _inside(path: str, roots) -> bool:
    return any(path == r or path.startswith(r.rstrip("/") + "/") for r in roots)


def cells_reporting(metric: dict, bench: dict) -> list:
    """Names of the cells that report ``metric``: its ``workloads``, or all."""
    return list(metric.get("workloads") or [c["name"] for c in bench["workloads"]])


def check(bench: dict, raw_bytes: int = 0) -> None:
    _need(raw_bytes <= MAX_BYTES, f"BENCHMARK.json is {raw_bytes} bytes, over {MAX_BYTES}")
    _need(set(bench) == TOP_KEYS, f"top-level keys {sorted(bench)}, wanted exactly {sorted(TOP_KEYS)}")

    paths, command = bench["paths"], bench["command"]
    _need(isinstance(paths, list) and 1 <= len(paths) <= 16, "paths: 1 to 16 directories")
    for p in paths:
        _need(isinstance(p, str) and PATH.match(p) and not p.startswith("/") and ".." not in p.split("/"),
              f"paths: {p!r} is not a relative path of permitted characters")
    _need(isinstance(command, list) and 1 <= len(command) <= 32, "command: 1 to 32 strings")
    for word in command:
        _line(word, "command word")
        _need(not word.startswith("/") and ".." not in word.split("/"), f"command: {word!r} leaves the repo")
        if "/" in word:
            _need(_inside(word, paths), f"command: {word!r} names a file outside paths")
    rs = bench["run_seconds"]
    _need(isinstance(rs, int) and not isinstance(rs, bool) and 1 <= rs <= MAX_RUN_SECONDS,
          f"run_seconds: a whole number from 1 to {MAX_RUN_SECONDS}, got {rs!r}")

    configs = bench["configs"]
    _need(isinstance(configs, list) and 1 <= len(configs) <= 24, "configs: 1 to 24")
    files = set()
    for c in configs:
        _keys(c, CONFIG_KEYS, set(), f"config {c.get('name')!r}")
        _name(c["name"], "config name")
        _line(c["source"], f"config {c['name']} source")
        _line(c["why"], f"config {c['name']} why")
        _need(isinstance(c["file"], str) and PATH.match(c["file"]) and _inside(c["file"], paths),
              f"config {c['name']}: file {c['file']!r} is not under paths")
        _need(c["file"] not in files, f"config {c['name']}: file {c['file']!r} is another configuration's")
        files.add(c["file"])
        _need(isinstance(c["reduced"], list) and len(c["reduced"]) <= 16, f"config {c['name']}: reduced has at most 16 keys")
        for key in c["reduced"]:
            _name(key, f"config {c['name']} reduced key")
            low = key.lower()
            _need(not (low.endswith("_dim") or low.endswith("_rank") or any(w in low for w in WIDTH_WORDS)),
                  f"config {c['name']}: reduced names a width: {key!r}")
    config_names = [c["name"] for c in configs]
    _need(len(set(config_names)) == len(config_names), "two configurations share a name")

    cells = bench["workloads"]
    _need(isinstance(cells, list) and 1 <= len(cells) <= 24, "workloads: 1 to 24 cells")
    pairs = set()
    for w in cells:
        _keys(w, CELL_KEYS, set(), f"cell {w.get('name')!r}")
        _name(w["name"], "cell name")
        _name(w["traffic"], f"cell {w['name']} traffic")
        _line(w["why"], f"cell {w['name']} why")
        _need(w["config"] in config_names, f"cell {w['name']}: unknown configuration {w['config']!r}")
        _need(w["chips"] in (1, 4), f"cell {w['name']}: chips is 1 or 4")
        _need((w["config"], w["traffic"]) not in pairs, f"cell {w['name']}: its configuration and traffic appear twice")
        pairs.add((w["config"], w["traffic"]))
    cell_names = [w["name"] for w in cells]
    _need(len(set(cell_names)) == len(cell_names), "two cells share a name")
    _need(set(config_names) == {w["config"] for w in cells}, "a configuration is used by no cell")
    four = sum(1 for w in cells if w["chips"] == 4)
    _need(four <= max(1, len(cells) // 4), f"{four} of {len(cells)} cells ask for 4 chips: at most a quarter, or one")

    e2e, layers = bench["end_to_end"], bench["per_layer"]
    _need(isinstance(e2e, list) and 1 <= len(e2e) <= 16, "end_to_end: 1 to 16 metrics")
    _need(isinstance(layers, list) and 1 <= len(layers) <= 128, "per_layer: 1 to 128 metrics")
    for m in e2e + layers:
        end = m in e2e
        _keys(m, E2E_KEYS if end else LAYER_KEYS, {"workloads"}, f"metric {m.get('name')!r}")
        _name(m["name"], "metric name")
        _need(isinstance(m["unit"], str) and UNIT.match(m["unit"]) is not None, f"metric {m['name']}: unit {m['unit']!r}")
        _need(m["better"] in ("lower", "higher"), f"metric {m['name']}: better is lower or higher")
        _need(m["source"] in (("host_clock", "device_trace") if end else SOURCES),
              f"metric {m['name']}: source {m['source']!r} is not permitted here")
        for cell in m.get("workloads", []):
            _need(cell in cell_names, f"metric {m['name']}: unknown cell {cell!r}")
        if "workloads" in m:
            _need(len(m["workloads"]) >= 1, f"metric {m['name']}: an empty workloads list")
    names = [m["name"] for m in e2e + layers]
    _need(len(set(names)) == len(names), "two metrics share a name")
    _need("setup_s" in [m["name"] for m in e2e], "end_to_end lacks setup_s")
    for m in e2e:
        b = m["bound"]
        _need(isinstance(b, (int, float)) and not isinstance(b, bool) and 0.01 <= b <= 0.1,
              f"metric {m['name']}: bound {b!r} is outside 0.01..0.1")
    setup = next(m for m in e2e if m["name"] == "setup_s")
    _need("workloads" not in setup, "setup_s is reported by every cell")
    by_name = {m["name"]: m for m in e2e}
    for m in layers:
        _line(m["layer"], f"metric {m['name']} layer")
        _need(m["moves"] in by_name, f"metric {m['name']}: moves {m['moves']!r} is no end-to-end metric")
        moved = set(cells_reporting(by_name[m["moves"]], bench))
        for cell in cells_reporting(m, bench):
            _need(cell in moved, f"metric {m['name']}: cell {cell} does not report {m['moves']}")
    for cell in cell_names:
        others = [m for m in e2e if m["name"] != "setup_s" and cell in cells_reporting(m, bench)]
        _need(others, f"cell {cell} reports no end-to-end metric besides setup_s")
        _need(any(cell in cells_reporting(m, bench) for m in layers), f"cell {cell} reports no per-layer metric")


def load(root: str) -> dict:
    """Read and check ``BENCHMARK.json`` at ``root``."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "rb") as f:
        raw = f.read()
    bench = json.loads(raw)
    check(bench, len(raw))
    return bench
