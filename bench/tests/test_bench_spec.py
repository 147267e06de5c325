"""BENCHMARK.json against the files the harness finds by its names."""
import json
import re

import pytest

from registry import BENCH_DIR, ROOT, load_cell, load_json, load_module

SPEC = load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
PER_LAYER = {m["name"]: m for m in SPEC["per_layer"]}


def test_names_and_keys():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert {m["name"] for m in SPEC["end_to_end"]} >= {"setup_s"}
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_with_every_file_it_names(cell):
    c = load_cell(cell)
    assert c.chips == 1
    assert c.config["arch"]["n_layers"] >= 1
    assert c.reference().last_logits and c.counter().flops
    lim = c.limits()["max_logit_gap"]
    assert lim["lower"] < lim["limit"] < lim["upper"]
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], "moves a metric the cell lacks")


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_metric_file_agrees_with_its_entry(name):
    mod = load_module(BENCH_DIR / "metrics" / f"{name}.py")
    entry = PER_LAYER[name]
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["name"], entry["unit"], entry["layer"], entry["moves"])
    if name.endswith("_roofline"):
        kernel = name[:-len("_roofline")]
        assert (BENCH_DIR / "kernels" / f"{kernel}.py").is_file()


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(config):
    f = load_json(ROOT / config["file"])
    assert f["source"] == config["source"]
    assert sorted(f["reduced"]) == sorted(config["reduced"])
    for key, cut in f["reduced"].items():
        assert f[key] != cut["published"]


#: published key -> the program's `ArchConfig` key, per reference family
ARCH_KEYS = {
    "qwen2": {"hidden_size": "d_model", "intermediate_size": "d_ff",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads",
              "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
              "rope_theta": "rope_theta"},
    "mamba2": {"d_model": "d_model", "n_layer": "n_layers"},
}


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_program_sizes_are_the_published_ones(config):
    f = load_json(ROOT / config["file"])
    arch = f["arch"]
    for pub, key in ARCH_KEYS[f["reference"]].items():
        assert arch[key] == f[pub], pub
    if f["reference"] == "qwen2":
        assert arch["d_head"] * arch["n_heads"] == f["hidden_size"]
    if f["reference"] == "mamba2":
        pad = f["pad_vocab_size_multiple"]
        assert arch["vocab_size"] == -(-f["vocab_size"] // pad) * pad
