"""Checks that ``BENCHMARK.json`` is well formed and that every name in
it resolves to a file under ``chipbench/``: each configuration's file and
reference, each cell's traffic mix, each metric's reader.

    python -m chipbench.validate     # prints the problems, exits 1 if any
"""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(s, n=200) -> bool:
    return isinstance(s, str) and 0 < len(s) <= n and "\n" not in s \
        and "\t" not in s


def problems(bench: dict, root: Path = CHECKOUT) -> list:
    out = []

    def need(ok, msg):
        if not ok:
            out.append(msg)

    need(set(bench) == KEYS["top"], f"top-level keys {sorted(bench)}")
    need(isinstance(bench.get("run_seconds"), int)
         and 1 <= bench["run_seconds"] <= 51, "run_seconds")
    need(all(_line(w) for w in bench["command"])
         and len(bench["command"]) <= 32, "command")
    cfgs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    need(len(cfgs) == len(bench["configs"]), "configuration names repeat")
    need(len(cells) == len(bench["workloads"]), "cell names repeat")
    names = list(e2e) + [m["name"] for m in bench["per_layer"]]
    need(len(set(names)) == len(names), "metric names repeat")
    for c in bench["configs"]:
        need(set(c) == KEYS["config"], f"config {c['name']} keys {sorted(c)}")
        need(NAME.match(c["name"]), f"config name {c['name']!r}")
        need(_line(c["source"]) and _line(c["why"]), f"config {c['name']}")
        f = root / c["file"]
        need(f.is_file(), f"config file {c['file']}")
        if f.is_file():
            body = json.loads(f.read_text())
            need(sorted(body.get("reduced", [])) == sorted(c["reduced"]),
                 f"config {c['name']}: reduced differs from its file")
            need((HERE / "configs" / f"{body['reference']}.py").is_file(),
                 f"config {c['name']}: no reference {body['reference']}")
        need(all(NAME.match(k) for k in c["reduced"]),
             f"config {c['name']}: reduced keys")
        need(any(w["config"] == c["name"] for w in bench["workloads"]),
             f"config {c['name']} used by no cell")
    pairs = set()
    for w in bench["workloads"]:
        need(set(w) == KEYS["workload"], f"cell {w['name']} keys")
        need(NAME.match(w["name"]) and NAME.match(w["traffic"]),
             f"cell {w['name']} names")
        need(w["config"] in cfgs, f"cell {w['name']}: unknown config")
        need(w["chips"] in (1, 4), f"cell {w['name']}: chips")
        need(_line(w["why"]), f"cell {w['name']}: why")
        need((HERE / "traffic" / f"{w['traffic']}.json").is_file(),
             f"cell {w['name']}: no traffic file")
        pairs.add((w["config"], w["traffic"]))
    need(len(pairs) == len(cells), "a (config, traffic) pair repeats")
    need("setup_s" in e2e, "no setup_s")
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            extra = set(m) - KEYS[kind]
            need(set(m) >= KEYS[kind] and extra <= {"workloads"},
                 f"{m['name']} keys")
            need(NAME.match(m["name"]) and UNIT.match(m["unit"]),
                 f"{m['name']} name or unit")
            need(m["better"] in ("lower", "higher"), f"{m['name']} better")
            need(m["source"] in (SOURCES_E2E if kind == "end_to_end"
                                 else SOURCES), f"{m['name']} source")
            need(all(c in cells for c in m.get("workloads", [])),
                 f"{m['name']}: unknown cell")
            need((HERE / "metrics" / f"{m['name']}.py").is_file(),
                 f"{m['name']}: no reader")
            if kind == "end_to_end":
                need(0.01 <= m["bound"] <= 0.25, f"{m['name']} bound")
            else:
                need(_line(m["layer"]), f"{m['name']} layer")
                need("workloads" in m, f"{m['name']} lists no cells")
                moved = e2e.get(m["moves"])
                need(moved is not None, f"{m['name']} moves no end-to-end")
                for c in m.get("workloads", []):
                    need(moved is None or "workloads" not in moved
                         or c in moved["workloads"],
                         f"{m['name']}: cell {c} does not report "
                         f"{m['moves']}")
    for c in cells:
        rep = [n for n, m in e2e.items()
               if "workloads" not in m or c in m["workloads"]]
        need("setup_s" in rep and len(rep) >= 2, f"cell {c}: end-to-end")
        need(any(c in m.get("workloads", []) for m in bench["per_layer"]),
             f"cell {c}: no per-layer metric")
    return out


def main() -> int:
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    found = problems(bench)
    for p in found:
        print(p)
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
