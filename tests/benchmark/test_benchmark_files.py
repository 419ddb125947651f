"""``BENCHMARK.json`` against the files it names: every configuration,
traffic mix and metric reader exists and parses, every name and unit uses
only the allowed characters, and the generator gives every seed the same
amount of work."""

import json
import os
import re

import pytest

from benchmark import layers, traffic
from benchmark.deployment import load_json

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


BENCH = bench()
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"])
    assert any(cfg["file"].startswith(p + "/") for p in BENCH["paths"])
    body = load_json("configs", cfg["name"])
    assert os.path.join(ROOT, cfg["file"]) == os.path.join(
        ROOT, "benchmark", "configs", cfg["name"] + ".json")
    assert body["name"] == cfg["name"]
    for key in cfg["reduced"]:
        assert NAME.match(key) and key in body and key in body["reduced"]
    for key in ("source", "replicas", "shards", "engine", "raft",
                "guarantees", "message_delay_ms", "chips", "assumed",
                "step_entries", "step_programs", "rehearsal"):
        assert key in body, key
    assert body["guarantees"]["replicas"] == 3
    assert body["expert"] == {}, "cells run the default ExpertConfig geometry"
    for text in (cfg["source"], cfg["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = load_json("traffic", cell["traffic"])
    traffic.validate(mix)
    traffic.validate({**mix, **mix.get("rehearsal", {})})
    assert load_json("configs", cell["config"])["chips"]["count"] == \
        cell["chips"]
    names = {m["name"] for m in METRICS
             if "workloads" not in m or cell["name"] in m["workloads"]}
    assert "setup_s" in names
    assert {m["name"] for m in BENCH["end_to_end"]} & names - {"setup_s"}
    assert {m["name"] for m in BENCH["per_layer"]} & names


def test_cells_and_names_are_unique():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_reader(metric):
    per_layer = metric in BENCH["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if per_layer:
        e2e = {m["name"]: m for m in BENCH["end_to_end"]}
        assert metric["moves"] in e2e
        moved = e2e[metric["moves"]]
        assert set(metric.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
        assert callable(layers.load_reader(metric["name"]))
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        assert callable(layers.load_reader(metric["name"], "end_to_end"))


def test_a_roofline_share_is_named_as_one():
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


@pytest.mark.parametrize("mix", ["write16", "write16-8x8", "mixed9to1"])
def test_every_seed_gets_the_same_work(mix):
    """Same reads and writes per block whatever the seed; same seed, same
    stream; a seed above 2**31 is fine."""
    params = load_json("traffic", mix)
    block = params["mix_block"]

    def first(seed, n=5 * block):
        stream = traffic.op_stream(params, seed, shard=3, gidx=2, first_key=0)
        return [next(stream) for _ in range(n)]

    a, b, c = first(1), first(2**31 + 12345), first(1)
    assert a == c and a != b
    for ops in (a, b):
        for i in range(0, len(ops), block):
            kinds = [k for k, _, _ in ops[i:i + block]]
            assert kinds.count("read") == round(
                params.get("read_share", 0.0) * block)
    for kind, key, value in a:
        assert len(key) == 7
        if kind == "write":
            assert len(traffic.command(key, value)) == params["payload_bytes"]
    if not params["key_space_per_shard"]:
        keys = [k for _, k, _ in a]
        assert len(set(keys)) == len(keys)


def test_generator_refuses_a_mix_it_cannot_drive():
    good = load_json("traffic", "mixed9to1")
    for bad in ({"loop": "open"}, {"read_share": 1.0}, {"payload_bytes": 8},
                {"key_space_per_shard": 0}, {"read_target": "nearest"}):
        with pytest.raises(ValueError):
            traffic.validate({**good, **bad})
