"""granite-4.0-h-micro on the CPU at small widths: the program's serving
path (prefill, then decode through both kinds of state) against the
plain reference's teacher-forced logits at every position, and the cell's
`correct` against the float8 control in the timed path."""
import json
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import run
from registry import BENCH_DIR, ROOT, load_cell, load_json, load_peaks

NAME = "granite-4.0-h-micro"
CELL = f"{NAME}.long_prompt"
PROMPT, TOTAL = 16, 24

#: f32 on both sides at the highest matmul precision: the program's
#: chunked SSD, online-softmax attention and caches against the plain
#: recurrence and full softmax differ by float32 rounding, 6e-7 to 8e-7
#: of the largest logit on either plan; one kind of state dropped after
#: prefill moves the decoded logits by 1e-3 (SSM state, which decays in
#: tens of steps) to 1.4 (conv state) of it (the fault tests below)
TOL = 1e-5


def small_f32(impl: str) -> dict:
    cell = load_cell(CELL)
    config = dict(cell.config)
    config["arch"] = dict(config["arch"], **cell.reference().SMALL_ARCH)
    config["plan"] = dict(config["plan"], compute_dtype="float32",
                          param_dtype="float32", kv_cache_dtype="float32",
                          attn_impl=impl, mlp_impl=impl, ssm_impl=impl)
    return config


def served_and_reference(impl: str, fault=None):
    """Logits (B, TOTAL - PROMPT + 1, V) of the last prompt position and
    every decoded one: the program's prefill then decode steps, and the
    reference over the whole sequence at once."""
    config = small_f32(impl)
    ref = load_cell(CELL).reference()
    params = jax.jit(lambda k: ref.make_params(config, k))(
        jax.random.PRNGKey(11))
    model = harness.build_model(config)
    toks = jax.random.randint(jax.random.PRNGKey(12), (2, TOTAL), 0,
                              config["arch"]["vocab_size"])
    with jax.default_matmul_precision("highest"):
        last, cache = jax.jit(model.prefill)(
            params, {"tokens": toks[:, :PROMPT]},
            model.init_cache(2, TOTAL))
        if fault:
            cache = fault(cache)
        got = [last]
        decode = jax.jit(model.decode_step)
        for t in range(PROMPT, TOTAL):
            lg, cache = decode(params, {"tokens": toks[:, t:t + 1],
                                        "pos": jnp.int32(t)}, cache)
            got.append(lg)
    want = ref.last_logits(config, params, np.asarray(toks),
                           n_last=TOTAL - PROMPT + 1)
    return np.stack(got, axis=1), np.asarray(want)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_matches_the_reference(impl):
    """Both destinations the plan can pick: the Pallas kernels (flash,
    ssd, swiglu reading the stacks) run in interpret mode."""
    got, want = served_and_reference(impl)
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


def _zero(kind: str):
    def fault(cache):
        scan = {slot: ({k: jnp.zeros_like(v) if k in kind else v
                        for k, v in c.items()})
                for slot, c in cache["scan"].items()}
        return {"scan": scan}
    return fault


@pytest.mark.parametrize("kind", [("k", "v"), ("ssm",), ("conv",)],
                         ids=["kv_cache", "ssm_state", "conv_state"])
def test_decode_reads_each_kind_of_state(kind):
    """With one kind of state dropped after prefill, decode departs from
    the reference by far more than `TOL`: the comparison sees each."""
    got, want = served_and_reference("xla", _zero(kind))
    err = np.abs(got[:, 1:] - want[:, 1:]).max() / np.abs(want).max()
    assert err > 100 * TOL


def _sizes_off(arch: dict = None, **published) -> set:
    f = dict(load_json(BENCH_DIR / "configs" / f"{NAME}.json"), **published)
    f["arch"] = dict(f["arch"], **(arch or {}))
    want = load_cell(CELL).reference().published_sizes(f)
    return {k for k, v in want.items() if f["arch"].get(k) != v}


@pytest.mark.parametrize("arch, off", [
    ({"attn_scale": 0.0}, "attn_scale"),
    ({"rope": True}, "rope"),
    ({"logit_scale": 8.0}, "logit_scale"),
    ({"ssm_act_bc": False}, "ssm_act_bc"),
    ({"norm_eps": 1e-6}, "norm_eps"),
    ({"layer_pattern": ["ssm"] * 4 + ["attn"] + ["ssm"] * 5},
     "layer_pattern"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_wrong_program_value_is_caught(arch, off):
    assert _sizes_off() == set()
    assert _sizes_off(arch) == {off}


@pytest.mark.parametrize("key, value", [
    ("mamba_n_groups", 2), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("hidden_act", "gelu")])
def test_a_published_block_the_program_cannot_run_is_refused(key, value):
    with pytest.raises(ValueError, match="the program runs"):
        _sizes_off(**{key: value})


# -- the cell's `correct` on the CPU ------------------------------------------

#: a small granite: one unit of the stack at widths the CPU runs in a few
#: seconds a wave
TINY = dict(n_layers=10, d_model=128, n_heads=4, n_kv_heads=2, d_head=32,
            d_ff=256, vocab_size=512, ssm_state=16, ssm_headdim=32,
            ssm_chunk=16)
TINY_TRAFFIC = {"loop": "lockstep_waves", "batch": 2, "prompt_tokens": 32,
                "output_tokens": 16, "why": "a tiny mix for the CPU tests"}
TINY_CELL = "tiny-granite.tiny"


def tiny_limit() -> dict:
    """At this size, over two waves a seed (seeds 1-6, four requests of 16
    served tokens compared), the program's widest gap read 4.9e-5 to
    1.07e-4 and the control's 0.99e-3 to 1.49e-3: the limit sits 2.8x
    above the one and 3.3x below the other."""
    return {"max_logit_gap": {"limit": 3e-4, "lower": 1.07e-4,
                              "upper": 0.99e-3}}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of BENCHMARK.json and bench/ with a tiny granite cell."""
    root = tmp_path_factory.mktemp("granite_copy")
    shutil.copytree(BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = load_json(ROOT / "BENCHMARK.json")
    config = load_json(BENCH_DIR / "configs" / f"{NAME}.json")
    config["arch"] = dict(config["arch"], **TINY)
    config["token_ids_below"] = 500
    for rel, text in {
            "configs/tiny-granite.json": json.dumps(config),
            "traffic/tiny.json": json.dumps(TINY_TRAFFIC),
            f"limits/{TINY_CELL}.json": json.dumps(tiny_limit())}.items():
        (root / "bench" / rel).write_text(text)
    spec["configs"].append({"name": "tiny-granite", "source": "test",
                            "file": "bench/configs/tiny-granite.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": TINY_CELL, "config": "tiny-granite",
                              "traffic": "tiny", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _fp8_reference_steps(ref, config, batch, cache_tokens):
    """The control in the program's place: each call runs the reference
    in float8 over the tokens fed so far and takes the argmax at the
    newest position."""
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


def run_tiny(root, seconds):
    cell = load_cell(TINY_CELL, root)
    return run.run_cell(cell, 2 ** 40 + 5, seconds, False, jax.devices(),
                        load_peaks("TPU v5 lite"), time.perf_counter())


@pytest.mark.parametrize("control", [False, True], ids=["program", "fp8"])
def test_fp8_control_in_the_timed_path_is_not_correct(tiny_root, monkeypatch,
                                                      control):
    """The program reads `correct`; the reference computed in float8 in its
    place does not, by the cell's one compared number."""
    if control:
        cell = load_cell(TINY_CELL, tiny_root)
        ref = cell.reference()
        monkeypatch.setattr(
            harness, "make_steps", lambda model, batch, cache_tokens:
            _fp8_reference_steps(ref, cell.config, batch, cache_tokens))
    result = run_tiny(tiny_root, seconds=4.0)
    gap = result["checks"]["max_logit_gap"]
    assert result["correct"] is not control, gap
    assert (gap["value"] > gap["limit"]) is control


# -- the two mixers' share of a prefill on the device trace -------------------

MS = 1e6        # the trace's clock counts nanoseconds
PRE = "jit_serve_prefill(3)"
BODY = "layers/while/body/closed_call/layer"


def hand_prefill() -> "ScopedTrace":
    """Two prefill calls of one period: per call five Mamba-2 layers'
    mixer ops (`layer/ssm`, 10 ms each, the ssd kernel among them), one
    attention layer's kernel (`layer/attn`, 30 ms) and its cache write
    (`layer/attn/kv_write`, 2 ms), the MLPs (`layer/mlp`, 40 ms) and the
    head (no mixer, 5 ms)."""
    from xscope import ScopedEvent, ScopedTrace
    from xtrace import Event

    ops, programs = [], []
    for t0 in (0, 200):
        t = t0
        for name, scope, dur in (
                [(f"fusion.{i}", f"{BODY}/ssm/conv", 6) for i in range(5)]
                + [(f"ssd_pallas.{i}", f"{BODY}/ssm/jit(ssd_pallas)", 4)
                   for i in range(5)]
                + [("flash_attention.1", f"{BODY}/attn/flash", 30),
                   ("scatter.1", f"{BODY}/attn/kv_write/scatter", 2),
                   ("swiglu_pallas.2", f"{BODY}/mlp/swiglu", 40),
                   ("fusion.9", "head/dot", 5)]):
            ops.append(ScopedEvent(name, t * MS, (t + dur) * MS, 0, PRE,
                                   scope=scope))
            t += dur
        programs.append(Event(PRE, t0 * MS, t * MS, 0))
    return ScopedTrace(ops=ops, programs=programs,
                       host=[Event("slice", 0, 400 * MS)], n_devices=1)


def ctx_for(trace):
    import traffic
    cell = load_cell(CELL)
    lo, hi = trace.span("slice")
    return run.MetricContext(trace=trace, lo=lo, hi=hi, cell=cell,
                             gen=traffic.generator(cell.traffic, 100, 0),
                             calls=[], peaks=load_peaks("TPU v5 lite"))


def test_mixer_prefill_ms_by_hand():
    c = ctx_for(hand_prefill())
    attn = c.cell.metric("attn_prefill_ms").read(c)
    ssm = c.cell.metric("ssm_prefill_ms").read(c)
    assert attn == pytest.approx(30 + 2)         # its cache write too
    assert ssm == pytest.approx(5 * (6 + 4))
    assert attn + ssm < c.cell.metric("prefill_ms").read(c)


@pytest.mark.parametrize("metric", ["attn_prefill_ms", "ssm_prefill_ms"])
def test_mixer_prefill_ms_reads_nothing_without_its_scope(metric):
    tr = hand_prefill()
    for o in tr.ops:
        o.scope = o.scope.replace("/attn", "").replace("/ssm", "")
    c = ctx_for(tr)
    assert c.cell.metric(metric).read(c) is None
    # nor from a slice with no prefill call
    tr = hand_prefill()
    tr.programs = []
    c = ctx_for(tr)
    assert c.cell.metric(metric).read(c) is None
