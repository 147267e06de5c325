"""What a configuration of a new model family brings goes in through added
files and entries alone: a copy of the benchmark gains a configuration
whose reference is a module no cell used before, a cell and its limits,
and the spec tests and the reference's CPU check pass on the copy
unedited.  The harness builds every architecture the program registers
from its JSON form, and the compile rehearsal compiles every kernel
module, a new one among them."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
import rehearse
from registry import BENCH_DIR, ROOT, load_json

FAMILY = "newfam"
CONFIG = "newfam-7b"
CELL = f"{CONFIG}.decode_heavy"

#: a new family's reference: its own published sizes, qwen2's equations
#: and small widths
REFERENCE = '''"""A new family's reference (a test's): qwen2's forward pass."""
from reference.qwen2 import SMALL_ARCH, last_logits, make_params  # noqa: F401


def published_sizes(f):
    return {"d_model": f["hidden_size"], "n_layers": f["num_hidden_layers"],
            "vocab_size": f["vocab_size"]}
'''
NO_SIZES = REFERENCE[:REFERENCE.index("def published_sizes")]
NO_SMALL = REFERENCE.replace("SMALL_ARCH, ", "")


def copy_with_new_family(root, reference: str):
    """A copy of BENCHMARK.json and bench/ with a configuration of a new
    family, its reference, a cell and its limits added; every file the
    copy had is left as it was."""
    shutil.copytree(BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    spec = load_json(ROOT / "BENCHMARK.json")
    config = load_json(BENCH_DIR / "configs" / "qwen2-7b.json")
    source = "https://example.org/newfam-7b/config.json"
    config.update(source=source, reference=FAMILY)
    config["arch"] = dict(config["arch"], name=CONFIG)
    new = {
        f"configs/{CONFIG}.json": json.dumps(config),
        f"reference/{FAMILY}.py": reference,
        f"limits/{CELL}.json": json.dumps(
            {"max_logit_gap": {"limit": 1.0, "lower": 0.2, "upper": 4.7}}),
    }
    for rel, text in new.items():
        assert not (root / "bench" / rel).exists()
        (root / "bench" / rel).write_text(text)
    spec["configs"].append({"name": CONFIG, "source": source,
                            "file": f"bench/configs/{CONFIG}.json",
                            "reduced": ["num_hidden_layers"],
                            "why": "a new family (test)"})
    spec["workloads"].append({"name": CELL, "config": CONFIG,
                              "traffic": "decode_heavy", "chips": 1,
                              "why": "a new family's cell (test)"})
    # the cell joins the metrics it reports by its name in their lists
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("tpot_p95_ms", "decode_ms"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert all(p.read_bytes() == b for p, b in before.items()), "an edit"


def bench_tests(root, *args):
    """The copy's own bench/tests, as it has them, in a process of its
    own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         *args],
        capture_output=True, text=True, env=env, cwd=root, timeout=300)


SPEC_TESTS = "bench/tests/test_bench_spec.py"
FORWARD_TEST = ("bench/tests/test_bench_references.py::"
                "test_reference_matches_program_forward")


def n_configs(root) -> int:
    return len(load_json(root / "BENCHMARK.json")["configs"])


def test_a_new_family_joins_by_added_files_alone(tmp_path):
    copy_with_new_family(tmp_path, REFERENCE)
    p = bench_tests(tmp_path, SPEC_TESTS, FORWARD_TEST)
    assert p.returncode == 0, p.stdout[-4000:]
    for test in (f"{SPEC_TESTS}::test_cell_loads_with_every_file_it_names"
                 f"[{CELL}]",
                 f"{SPEC_TESTS}::test_config_file_states_its_cut[{CONFIG}]",
                 f"{SPEC_TESTS}::test_program_sizes_are_the_published_ones"
                 f"[{CONFIG}]",
                 f"{FORWARD_TEST}[{CELL}]"):
        assert f"PASSED {test}" in p.stdout


def test_a_reference_without_published_sizes_names_what_to_add(tmp_path):
    copy_with_new_family(tmp_path, NO_SIZES)
    p = bench_tests(tmp_path, SPEC_TESTS, "-k", "published_ones")
    assert p.returncode == 1
    assert (f"FAILED {SPEC_TESTS}::"
            f"test_program_sizes_are_the_published_ones[{CONFIG}]") in p.stdout
    assert f"reference/{FAMILY}.py has no published_sizes(" in p.stdout
    assert "KeyError" not in p.stdout
    # the new family fails, and no configuration the benchmark had
    assert f"1 failed, {n_configs(tmp_path) - 1} passed" in p.stdout


def test_a_reference_without_small_widths_names_what_to_add(tmp_path):
    copy_with_new_family(tmp_path, NO_SMALL)
    p = bench_tests(tmp_path, FORWARD_TEST)
    assert p.returncode == 1
    assert f"FAILED {FORWARD_TEST}[{CELL}]" in p.stdout
    assert f"reference/{FAMILY}.py has no SMALL_ARCH" in p.stdout
    assert f"1 failed, {n_configs(tmp_path) - 1} passed" in p.stdout


def _registered():
    from repro.configs.base import list_archs
    return [(name, reduced) for name in list_archs()
            for reduced in (False, True)]


@pytest.mark.parametrize("name, reduced", _registered(),
                         ids=lambda v: v if isinstance(v, str)
                         else ("reduced" if v else "full"))
def test_every_registered_arch_builds_from_its_json_form(name, reduced):
    from repro.configs.base import get_config
    cfg = get_config(name, reduced=reduced)
    arch = dataclasses.asdict(cfg)
    plan = arch.pop("plan")
    config = json.loads(json.dumps({"arch": arch, "plan": plan}))
    assert harness.build_model(config).cfg == cfg


def test_rehearsal_compiles_every_kernel_module(tmp_path, monkeypatch):
    """A kernel module that a later change adds is compiled in the
    rehearsal, not interpreted."""
    import repro.kernels as K
    (tmp_path / "new_kernel.py").write_text(
        "from repro.kernels import resolve_interpret  # noqa: F401\n")
    monkeypatch.setattr(K, "__path__", [*K.__path__, str(tmp_path)])
    try:
        mods = rehearse.kernel_modules()
        for mod in mods:    # restored when the test ends
            monkeypatch.setattr(mod, "resolve_interpret",
                                mod.resolve_interpret)
        assert {m.__name__.rsplit(".", 1)[-1] for m in mods} >= {
            "kernels", "ops", "flash_attention", "swiglu", "ssd", "rglru",
            "mriq", "new_kernel"}
        assert all(m.resolve_interpret(None) is True for m in mods)
        rehearse.compile_for_chip()
        assert all(m.resolve_interpret(None) is False for m in mods)
        assert all(m.resolve_interpret(True) is True for m in mods)
    finally:
        sys.modules.pop(f"{K.__name__}.new_kernel", None)
        K.__dict__.pop("new_kernel", None)
