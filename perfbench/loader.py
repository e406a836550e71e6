"""Find a cell's files by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(path: str):
    name = "perfbench_" + re.sub(r"\W", "_", os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sibling(file: str, name: str):
    return load_module(os.path.join(os.path.dirname(file), name))


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def sizes(bench: dict, config: str) -> dict:
    with open(os.path.join(ROOT, config_entry(bench, config)["file"])) as f:
        return json.load(f)


def config_module(config: str):
    return load_module(os.path.join(HERE, "configs", f"{config}.py"))


def limits(cell: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{cell}.json")) as f:
        return json.load(f)


def metric_reader(name: str):
    return load_module(os.path.join(HERE, "metrics", f"{name}.py"))
