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
    assert c.chips in (1, 4)
    four = [w["name"] for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2), four
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


def sizes_off(f: dict) -> dict:
    """The `arch` sizes of configuration file `f` that differ from what its
    published keys require, by the reference family's `published_sizes`:
    {key: (in the file, required)}."""
    family = f["reference"]
    ref = load_module(BENCH_DIR / "reference" / f"{family}.py")
    assert hasattr(ref, "published_sizes"), (
        f"bench/reference/{family}.py has no published_sizes(f: dict) -> "
        "dict: add one that maps each arch key to the value the "
        "configuration's published keys require")
    arch = f["arch"]
    return {k: (arch.get(k), v) for k, v in ref.published_sizes(f).items()
            if arch.get(k) != v}


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_program_sizes_are_the_published_ones(config):
    assert sizes_off(load_json(ROOT / config["file"])) == {}


@pytest.mark.parametrize("config, arch, off", [
    ("qwen2-7b", {"d_head": 100}, "d_head"),     # 100 does not divide 3584
    ("qwen2-7b", {"n_heads": 32, "d_head": 112}, "n_heads"),
    ("qwen2-7b", {"rope_theta": 10000.0}, "rope_theta"),
    ("mamba2-1.3b", {"vocab_size": 50277}, "vocab_size"),   # unpadded
    ("mamba2-1.3b", {"n_layers": 24}, "n_layers"),
])
def test_a_wrong_program_size_is_caught(config, arch, off):
    f = load_json(BENCH_DIR / "configs" / f"{config}.json")
    f["arch"] = dict(f["arch"], **arch)
    assert off in sizes_off(f)


def test_heads_that_do_not_divide_the_width_are_caught():
    f = load_json(BENCH_DIR / "configs" / "qwen2-7b.json")
    f["num_attention_heads"] = f["arch"]["n_heads"] = 27
    with pytest.raises(ValueError, match="no multiple"):
        sizes_off(f)
