"""Finds everything of a run by name: the cell in `BENCHMARK.json`, its
configuration, the family of its architecture, traffic mix and cell file,
and the reader of each metric.

    BENCHMARK.json                       cells, metrics and what each moves
    bench/configs/<config>.json          sizes, dtype, slots (file named
                                         there)
    bench/families/<architecture>.py     sizes, weights' leaves, the
                                         program's config, costs and the
                                         plain reference of one
                                         architecture, named by the
                                         configuration's ``architectures[0]``
    bench/traffic/<mix>.json             the generator's parameters
    bench/cells/<cell>.json              the cell's KV ring and its limits
    bench/metrics/<metric>.py            ``read(run) -> float | None``

A later cell, configuration, architecture, mix or metric is a new file
and a new entry; nothing here changes for it.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


def _ident(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


class Bench:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / "bench"

    def _named(self, key: str, name: str) -> dict:
        for entry in self.doc[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"no {key[:-1]} named {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._named("configs", name)
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def cell_file(self, name: str) -> dict:
        return json.loads((self.dir / "cells" / f"{name}.json").read_text())

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.doc["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> list[dict]:
        moved = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.doc["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]

    def family(self, config: str):
        """The family module of a configuration's architecture; the
        docstring of each module under `bench/families/` lists what one
        provides."""
        arch = self.config(config)["architectures"][0]
        return _load(self.dir / "families" / f"{arch}.py",
                     f"bench_family_{_ident(arch)}")

    def reader(self, metric: str):
        return _load(self.dir / "metrics" / f"{metric}.py",
                     f"bench_metric_{_ident(metric)}").read
