"""BENCHMARK.json against the rules its harness and its readers rely on:
every cell finds its configuration, traffic mix and limits, every metric
its reader, and every per-layer metric an end-to-end metric it moves in
each of its cells."""
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys():
    assert set(_spec()) == {"command", "paths", "run_seconds", "configs",
                            "workloads", "end_to_end", "per_layer"}


def test_cells_find_their_files():
    spec = _spec()
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert NAME.fullmatch(c["name"])
        assert c["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    seen = set()
    for w in spec["workloads"]:
        assert NAME.fullmatch(w["name"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert w["config"] in configs
        for sub, name in (("traffic", w["traffic"]),
                          ("limits", w["name"])):
            assert os.path.isfile(os.path.join(BENCH, sub, name + ".json"))
        assert len(w["why"]) <= 200
    used = {w["config"] for w in spec["workloads"]}
    assert used == set(configs)


def test_config_files_state_their_cuts():
    spec = _spec()
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in ("source", "assumed", "guarantees", "chips"):
            assert key in cfg


def test_metrics_have_readers_and_move_a_reported_metric():
    spec = _spec()
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = set()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.fullmatch(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for cell in cells:
        reported = [m for m in spec["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2
    for m in spec["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells)
