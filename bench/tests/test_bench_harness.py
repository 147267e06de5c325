"""The harness end to end on the CPU at a tiny size, in a copy of the
benchmark to which a configuration, a traffic mix, a limit and a metric
are added as new files: the harness takes them without an edit to any
file it already has.  With the timed path broken underneath, `correct`
comes out false."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import harness
import run
from registry import BENCH_DIR, ROOT, load_cell, load_json, load_peaks

TINY_QWEN2 = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                  d_head=32, d_ff=256, vocab_size=512)
TINY_TRAFFIC = {"loop": "lockstep_waves", "batch": 2, "prompt_tokens": 16,
                "output_tokens": 8, "why": "a tiny mix for the CPU tests"}
CELL = "tiny-qwen2.tiny"

NEW_METRIC = '''"""Decode calls in the traced slice (a test's new metric)."""
NAME = "decode_calls"
UNIT = "calls"
LAYER = "serving step programs"
MOVES = "tpot_p95_ms"


def read(ctx):
    return float(sum(1 for prog, _ in ctx.calls if prog == "decode"))
'''

COUNTER_METRIC = '''"""Traces whose swiglu read the stacked weights, a `repro.obs`
counter (a test's new metric)."""
NAME = "stacked_swiglu_traces"
UNIT = "traces"
LAYER = "kernels"
MOVES = "tpot_p95_ms"


def read(ctx):
    return ctx.counters.get("kernel_stacked_swiglu")
'''


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of BENCHMARK.json and bench/, plus new files and entries."""
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    spec = load_json(ROOT / "BENCHMARK.json")
    config = load_json(BENCH_DIR / "configs" / "qwen2-7b.json")
    config["arch"] = dict(config["arch"], **TINY_QWEN2)
    config["token_ids_below"] = 500
    config["init"] = dict(config["init"], std=0.1)
    new = {
        "configs/tiny-qwen2.json": json.dumps(config),
        "traffic/tiny.json": json.dumps(TINY_TRAFFIC),
        f"limits/{CELL}.json": json.dumps(
            {"max_logit_gap": {"limit": 0.2, "lower": 0.02, "upper": 2.6}}),
        "metrics/decode_calls.py": NEW_METRIC,
        "metrics/stacked_swiglu_traces.py": COUNTER_METRIC,
    }
    for rel, text in new.items():
        (root / "bench" / rel).write_text(text)
    spec["configs"].append({"name": "tiny-qwen2", "source": "test",
                            "file": "bench/configs/tiny-qwen2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-qwen2",
                              "traffic": "tiny", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "tpot_p95_ms":
            m["workloads"].append(CELL)
    spec["per_layer"].append({"name": "decode_calls", "unit": "calls",
                              "better": "higher", "source": "device_trace",
                              "layer": "serving step programs",
                              "moves": "tpot_p95_ms", "workloads": [CELL]})
    spec["per_layer"].append({"name": "stacked_swiglu_traces",
                              "unit": "traces", "better": "higher",
                              "source": "program_counter", "layer": "kernels",
                              "moves": "tpot_p95_ms", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / "bench").rglob("*") if p.is_file()}
    assert all(after[k] == v for k, v in before.items()), "an edit"
    return root


def run_tiny(root, trace=False, tmp_path=None, seconds=1.0):
    cell = load_cell(CELL, root)
    kw = {"trace_dir": tmp_path / "trace"} if trace else {}
    return run.run_cell(cell, 2 ** 40 + 3, seconds, trace, jax.devices(),
                        load_peaks("TPU v5 lite"), time.perf_counter(), **kw)


def test_new_cell_runs_and_is_correct(tiny_root):
    result = run_tiny(tiny_root)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"output_tokens_per_s", "tpot_p95_ms", "setup_s"} <= set(
        result["metrics"])
    assert list(result)[-1] == "checks"
    assert result["checks"]["max_logit_gap"]["limit"] == 0.2


def test_new_metric_is_read_in_a_traced_run(tiny_root, tmp_path):
    result = run_tiny(tiny_root, trace=True, tmp_path=tmp_path)
    assert result["correct"] is True
    # the tiny mix's first wave: 7 decode steps, all in the traced slice
    assert result["metrics"]["decode_calls"]["value"] == 7.0
    # read from `repro.obs`: the prefill's trace; the decode step's 2 rows
    # are too few for the kernel and take its oracle
    assert result["metrics"]["stacked_swiglu_traces"]["value"] == 1
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def _state_unchanged(steps, vocab):
    decode = steps.decode
    return jax.jit(lambda p, t, pos, c: (decode(p, t, pos, c)[0], c))


def _token_altered(steps, vocab):
    decode = steps.decode

    def altered(p, t, pos, c):
        tok, c = decode(p, t, pos, c)
        return (tok + 1) % vocab, c
    return jax.jit(altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    real = harness.make_steps

    def broken(model, batch, cache_tokens):
        steps = real(model, batch, cache_tokens)
        steps.decode = fault(steps, model.cfg.vocab_size)
        return steps
    monkeypatch.setattr(harness, "make_steps", broken)
    result = run_tiny(tiny_root)
    assert result["correct"] is False
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def _fp8_reference_steps(ref, config, batch, cache_tokens):
    """The control of `correct`: the plain reference computed in float8 put
    in the program's place.  Its cache is the tokens fed so far; each call
    runs the causal reference over the whole buffer (one shape) and takes
    the argmax at the newest position."""
    import jax.numpy as jnp

    def argmax_at(params, toks, pos):
        logits = ref.last_logits(config, params, toks, n_last=cache_tokens,
                                 mode="fp8")
        return jnp.argmax(logits[:, pos], axis=-1).astype(jnp.int32)

    def prefill(params, tokens, cache):
        cache = cache.at[:, :tokens.shape[1]].set(tokens)
        return argmax_at(params, cache, tokens.shape[1] - 1), cache

    def decode(params, tok, pos, cache):
        cache = cache.at[:, int(pos)].set(tok)
        return argmax_at(params, cache, int(pos)), cache

    return harness.Steps(
        new_cache=lambda: jnp.zeros((batch, cache_tokens), jnp.int32),
        prefill=prefill, decode=decode)


def test_fp8_control_in_the_timed_path_is_not_correct(tiny_root,
                                                      monkeypatch):
    cell = load_cell(CELL, tiny_root)
    ref = cell.reference()
    monkeypatch.setattr(
        harness, "make_steps", lambda model, batch, cache_tokens:
        _fp8_reference_steps(ref, cell.config, batch, cache_tokens))
    result = run_tiny(tiny_root, seconds=2.0)
    assert result["correct"] is False
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("prompt, out, cache, requests", [
    (2048, 32, 3072, 16), (128, 256, 384, 2), (16, 8, 24, 64)])
def test_cache_and_check_sizes_follow_the_mix(prompt, out, cache, requests):
    import check
    import traffic
    from repro.configs.base import PlanConfig
    gen = traffic.generator(dict(TINY_TRAFFIC, prompt_tokens=prompt,
                                 output_tokens=out), 100, 1)
    plan = PlanConfig(attn_chunk=1024)
    assert harness.cache_length(plan, gen.cache_tokens) == cache
    assert check.check_requests(gen) == requests


def _bench_cmd(root):
    return [sys.executable, str(root / "bench" / "run.py"), "--workload",
            "qwen2-7b.prompt_heavy", "--seed", "1", "--seconds", "1",
            "--trace", "0"]


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(_bench_cmd(ROOT), capture_output=True, text=True,
                       env=env, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "platform=cpu" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """Without the program beside it, the benchmark prints no result."""
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(_bench_cmd(tmp_path), capture_output=True, text=True,
                       env=env, timeout=300, cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


SMALL_QWEN2 = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
                   d_head=64, d_ff=512, vocab_size=2048)


@pytest.mark.parametrize("seed", [1, 2])
def test_fp8_control_reads_far_above_the_program(seed):
    """The control of step 2: the reference in float8 in the program's
    place.  At a size the CPU can hold (4 requests of 32 prompt and 32
    served tokens), its widest gap reads over three times the program's,
    as it does on the chip at the cells' sizes."""
    import check
    import traffic
    cell = load_cell("qwen2-7b.prompt_heavy")
    config = dict(cell.config, token_ids_below=2000)
    config["arch"] = dict(config["arch"], **SMALL_QWEN2)
    ref = cell.reference()
    gen = traffic.generator(
        dict(TINY_TRAFFIC, batch=4, prompt_tokens=32, output_tokens=32),
        2000, seed)
    params = jax.jit(lambda k: ref.make_params(config, k))(
        harness.seed_key(seed))
    steps = harness.make_steps(harness.build_model(config), gen.batch,
                               gen.cache_tokens)
    win = harness.Window(0.0, float("inf"), [])
    win.waves.append(harness.Wave(0, 0.0, gen.output_tokens))
    harness.run_wave(steps, params, gen, win.waves[0], float("inf"))
    sample = check.draw_sample(win, gen, seed)
    program = check.program_gap(ref, config, params, sample)
    control = check.control_gap(ref, config, params, sample)
    assert control > 3 * program
