"""BENCHMARK.json against the benchmark's contract, and every cell
resolved to its files by name alone."""
import ast
import os
import re
import subprocess
import sys

import pytest

from perfbench import loader, traffic

ROOT = loader.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = loader.benchmark()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["perfbench"]
    assert BENCH["command"][1:] == ["perfbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k) and not k.endswith(("_dim", "_rank")), k


def test_metric_entries():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m["workloads"]:
            # the cell reports the metric this one moves
            assert cell in e2e[m["moves"]].get("workloads", [cell])
        if m["name"].endswith("_roofline") or "roofline" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(cell):
    wl = loader.workload(BENCH, cell)
    assert wl["chips"] in (1, 4) and 1 <= len(wl["why"]) <= 200
    sz = loader.sizes(BENCH, wl["config"])
    entry = loader.config_entry(BENCH, wl["config"])
    assert entry["file"].startswith("perfbench/")
    assert sz["name"] == wl["config"] and sz["reduced"] == entry["reduced"]
    mod = loader.config_module(wl["config"])
    mix = traffic.load_mix(wl["traffic"])
    lim = loader.limits(cell)
    assert lim["limits"] and all(v > 0 for v in lim["limits"].values())
    # what the cell reports: its rate, setup_s and at least one per-layer
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if cell in m.get("workloads", [cell])]
    assert mix["rate"] in e2e and "setup_s" in e2e
    assert any(cell in m.get("workloads", []) for m in BENCH["per_layer"])
    assert mod.unit_work(sz, mix)[mix["rate"]] > 0
    assert mod.unit_flops(sz, mix) > 0 and mod.unit_calls(sz, mix)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_resolves(metric):
    assert callable(loader.metric_reader(metric).read)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_reference_imports_nothing_of_the_program(config):
    path = os.path.join(loader.HERE, "configs", f"{config}.reference.py")
    tree = ast.parse(open(path).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        else:
            continue
        for m in mods:
            assert not m.startswith(("repro", "perfbench")), m


def test_run_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "resnet18-imagenet.eval",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "{" not in p.stdout
    assert "no TPU" in p.stderr
