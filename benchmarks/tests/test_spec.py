"""Every entry of BENCHMARK.json resolves to its files by name, and the file
keeps to the limits of the contract that can be checked without a chip."""

import json
import os
import re

import pytest

from ddbench import flops, spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {c["name"] for c in BENCH["configs"]} \
        == {w["config"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_resolves_to_its_files(name):
    cell = spec.Cell(BENCH, name)
    assert cell.chips in (1, 4)
    assert 1 <= len(cell.entry["why"]) <= 200
    assert os.path.exists(cell.config_path)
    assert os.path.exists(cell.traffic_path)
    family = cell.family()
    for attr in ("UNIT", "shard", "reference_rows", "open_dataset", "build"):
        assert hasattr(family, attr), attr
    mesh = cell.traffic["mesh"]
    chips = 1
    for size in mesh.values():
        chips *= size
    assert chips == cell.chips
    for group in ("end_to_end", "per_layer"):
        names = cell.metric_names(group)
        assert names, group
        for metric in names:
            assert callable(spec.load_module("metrics", metric).read)
    assert "setup_s" in cell.metric_names("end_to_end")
    assert len(cell.metric_names("end_to_end")) >= 2


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_states_its_cuts(entry):
    assert entry["file"].startswith("benchmarks/configs/")
    cfg = json.load(open(os.path.join(spec.ROOT, entry["file"])))
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    for key in entry["reduced"]:
        assert not re.search(r"(_dim|_rank|hidden|intermediate|head_dim)$",
                             key), f"{key} is a width"
    for key in ("source", "assumed", "deployment", "guarantees", "loss_rtol",
                "loss_rtol_reason", "family"):
        assert key in cfg, key
    assert 1 <= len(entry["source"]) <= 200 and 1 <= len(entry["why"]) <= 200


def test_metrics_are_well_formed():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in BENCH[g]]
    assert len(set(names)) == len(names)
    layers = set()
    for group in ("end_to_end", "per_layer"):
        for m in BENCH[group]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        layers.add(m["layer"])
        moved = e2e[m["moves"]]
        # the moved metric is reported wherever this one is
        assert set(m.get("workloads", WORKLOADS)) \
            <= set(moved.get("workloads", WORKLOADS))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    perf = open(os.path.join(spec.ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_peaks_table():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


def test_flop_counts():
    # bench.py:_lm_flops_per_step at S=8192 b=2, worked by hand in PERF.md.
    got = flops.lm_flops_per_step(32768, 1024, 8, 2, 8192)
    t = 2 * 8192
    fwd = 8 * (24 * t * 1024 ** 2 + 2 * 2 * 8192 ** 2 * 1024) \
        + 2 * t * 1024 * 32768
    assert got == 3.0 * fwd
    fl, by = flops.flash_flops_bytes_per_step(8, 2, 16, 8192, 64)
    assert fl == 18.0 * 64 * (8192 * 8193 // 2) * 2 * 16 * 8
    # compute-bound on the v5e: FLOPs over peak far above bytes over peak
    assert fl / 197e12 > 10 * by / 819e9
