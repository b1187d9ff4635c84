"""BENCHMARK.json: every name resolves to its files, and the file keeps
the benchmark's own rules (names, units, keys, metric readers)."""
import json
import os
import re

import pytest

import _paths  # noqa: F401
from chipbench import model, registry, traffic

BENCH = registry.load(_paths.ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=[w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_by_name(cell):
    config = model.load_config(registry.config_file(_paths.ROOT, BENCH,
                                                    cell))
    assert config["name"] == cell["config"]
    m = model.model_of(config)
    assert m.n_heads % m.n_kv_heads == 0
    for key in ("batch_size", "block_size", "kv_pool_blocks",
                "prefill_group_cap", "prefix_cache"):
        assert key in config["engine"]
    assert config["correct"]["mean_logit_gap"] > 0
    mix = traffic.load_mix(registry.traffic_file(_paths.BENCH, cell))
    t = traffic.Traffic(mix, m.vocab, 1)
    assert t.max_len() <= 8192
    for kind in ("end_to_end", "per_layer"):
        got = registry.metrics_for(BENCH, cell, kind)
        assert got, (cell["name"], kind)
        if kind == "per_layer":
            for metric in got:
                assert callable(registry.reader(_paths.BENCH,
                                                metric["name"]))
    names = [m["name"] for m in registry.metrics_for(BENCH, cell,
                                                     "end_to_end")]
    assert "setup_s" in names and len(names) >= 2


def test_names_units_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(_paths.ROOT, c["file"]))
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["name"].split(".")[0].endswith("_roofline") \
                or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        for name in m["workloads"]:
            e2e = {e["name"] for e in registry.metrics_for(
                BENCH, cells[name], "end_to_end")}
            assert m["moves"] in e2e, (m["name"], name)
