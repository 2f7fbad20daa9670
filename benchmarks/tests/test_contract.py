"""What the contract asks of the files and of the command."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks.harness import spec


def test_every_cell_finds_its_files():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    for w in bm["workloads"]:
        cell = spec.Cell(w["name"])
        assert cell.config_entry["reduced"] == []
        assert {m["name"] for m in cell.end_to_end()} == {
            "train_tokens_per_s", "round_s", "setup_s"}
        for m in cell.per_layer():
            assert "reducer" in m or cell.metric_reader(m["name"])
        assert set(cell.limits["limits"]) <= {
            "loss", "count", "grad", "grad2", "change"}


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text, most=200):
    return 1 <= len(text) <= most and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_to_the_contract():
    """The limits the driver refuses a file over, before any run."""
    path = os.path.join(spec.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        bm = json.load(f)
    assert bm["paths"] == ["benchmarks"] and 1 <= bm["run_seconds"] <= 51
    assert len(bm["command"]) <= 32 and all(_line(w) for w in bm["command"])
    # 2 + 14 x 24 runs of run_seconds + 60 s, 2 x 90 s a cell, 1200 s spare
    assert (2 + 14 * 24) * (bm["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    configs = {c["name"]: c for c in bm["configs"]}
    assert len(configs) == len(bm["configs"]) <= 24
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmarks/") and os.path.exists(
            os.path.join(spec.ROOT, c["file"]))
    cells = [w["name"] for w in bm["workloads"]]
    assert len(set(cells)) == len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in bm["workloads"]}) == len(cells)
    assert {w["config"] for w in bm["workloads"]} == set(configs)
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(set(names)) == len(names)
    assert 1 <= len(bm["end_to_end"]) <= 16 and 1 <= len(bm["per_layer"]) <= 128
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    e2e = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in e2e
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e and _line(m["layer"])
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    # a kernel's share of its roofline and the whole step's share of the peak
    assert any(m["name"].endswith("_roofline") and m["unit"] == "%"
               for m in bm["per_layer"])
    assert any("mfu" in re.split(r"[_.]", m["name"]) for m in bm["per_layer"])


def test_added_by_files_alone(tiny_root):
    """One cell, configuration, mix and metric added to a copy by new files
    and new entries (see ``conftest.tiny_root``) load with no edit."""
    cell = spec.Cell("tiny.round-tiny", root=tiny_root)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["seq_len"] == 32
    names = [m["name"] for m in cell.per_layer()]
    assert "rounds_traced" in names and "round_mfu" in names
    assert cell.metric_reader("rounds_traced")({"rounds": 3}) == 3.0
    # and a metric scoped to other cells stays out of this repo's own
    old = spec.Cell("yi-6b.round-short", root=tiny_root)
    assert "rounds_traced" not in [m["name"] for m in old.per_layer()]


def test_unknown_device_kind_is_an_error():
    assert spec.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        spec.load_peaks("TPU v99")


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmarks", "run.py"),
         "--workload", "yi-6b.round-short", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr
