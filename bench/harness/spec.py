"""Find a cell's pieces by the names in ``BENCHMARK.json``.

    bench/configs/<config>.json     sizes as run, source, reference module
    bench/traffic/<traffic>.json    the mix (see harness/traffic.py)
    bench/limits/<workload>.json    the limits ``correct`` is judged by
    bench/metrics/<metric>.py       one per-layer metric: read(ctx) -> float | None
    bench/kernels/<kernel>.py       flops() / bytes_moved() of one kernel
    bench/reference/<module>.py     a plain reference: logits(sizes, weights, tokens)

A later cell, mix, metric or kernel is a new file and a new entry; no file
here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    bench: dict           # the whole BENCHMARK.json
    workload: dict        # its entry under "workloads"
    config: dict          # bench/configs/<config>.json
    mix: dict             # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<workload>.json

    @property
    def sizes(self) -> dict:
        return self.config["as_run"]

    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list[dict]:
        """Per-layer metrics this cell reports: those listing it, and those
        without a list that move an end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [
            m for m in self.bench["per_layer"]
            if (self.name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)
        ]

    def reference(self):
        return load_module(
            BENCH / "reference" / f"{self.config['reference']}.py"
        )


def load(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        known = sorted(w["name"] for w in bench["workloads"])
        raise SystemExit(f"unknown workload {name!r}; known: {known}")
    w = found[0]
    return Cell(
        name=name, bench=bench, workload=w,
        config=_json(BENCH / "configs" / f"{w['config']}.json"),
        mix=_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=_json(BENCH / "limits" / f"{name}.json"),
    )


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py").read


def kernel(name: str):
    return load_module(BENCH / "kernels" / f"{name}.py")


def peaks(device_kind: str) -> dict:
    table = _json(BENCH / "harness" / "peaks.json")["chips"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return table[device_kind]
