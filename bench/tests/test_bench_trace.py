"""The reduction from trace to metrics, on a hand-made trace whose numbers
can be worked by hand, and on a small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

import run
import traffic
from registry import load_cell, load_module, load_peaks
from xtrace import Event, Trace, program_name

MS = 1e6        # the trace's clock counts nanoseconds
DATA = Path(__file__).resolve().parent / "data"


def hand_trace() -> Trace:
    def ev(name, a, b, program=""):
        return Event(name, a * MS, b * MS, 0, program)
    pre, dec = "jit_serve_prefill(3)", "jit_serve_decode(4)"
    return Trace(
        programs=[ev(pre, 0, 100), ev(dec, 120, 150), ev(dec, 160, 190)],
        ops=[ev("flash_attention.3", 10, 40, pre),
             ev("fusion.1", 40, 90, pre),
             ev("swiglu_pallas.2", 125, 145, dec),
             ev("fusion.2", 165, 185, dec),
             ev("fusion.2", 170, 180, dec)],       # overlaps: counted once
        host=[ev("slice", 0, 200), ev("prefill", 0, 8),
              ev("token_pull", 90, 118), ev("decode_dispatch", 150, 162)],
        n_devices=1)


def ctx_for(trace, cell_name="qwen2-7b.prompt_heavy", calls=()):
    cell = load_cell(cell_name)
    gen = traffic.generator(cell.traffic, 100, 0)
    lo, hi = trace.span("slice")
    return run.MetricContext(trace=trace, lo=lo, hi=hi, cell=cell, gen=gen,
                             calls=list(calls),
                             peaks=load_peaks("TPU v5 lite"))


COUNTER_METRIC = '''"""Traces whose swiglu read the stacked weights (a test's)."""


def read(ctx):
    return ctx.counters.get("kernel_stacked_swiglu")
'''


def test_a_metric_reads_a_program_counter(tmp_path):
    (tmp_path / "stacked_swiglu_traces.py").write_text(COUNTER_METRIC)
    metric = load_module(tmp_path / "stacked_swiglu_traces.py")
    c = ctx_for(hand_trace())
    assert c.counters == {}
    assert metric.read(c) is None           # nothing to read: no number
    c.counters = {"kernel_stacked_swiglu": 2.0, "kernel_fallback_ssd": 1.0}
    assert metric.read(c) == 2.0


def test_busy_union_and_idle_share():
    tr = hand_trace()
    # busy 30 + 50 + 20 + 20 of 200 ms: the overlapping op adds nothing
    assert tr.busy_ns(0, 200 * MS) == pytest.approx(120 * MS)
    idle = load_cell("qwen2-7b.prompt_heavy").metric("device_idle_share")
    assert idle.read(ctx_for(tr)) == pytest.approx(40.0)


def test_program_time_per_call():
    c = ctx_for(hand_trace())
    cell = c.cell
    assert cell.metric("prefill_ms").read(c) == pytest.approx(100.0)
    assert cell.metric("decode_ms").read(c) == pytest.approx(30.0)


def test_idle_gaps_named_by_the_host_span_they_fall_in():
    gaps = hand_trace().breakdown(0, 200 * MS)["idle_gaps"]
    assert [g[0] for g in gaps] == ["token_pull", "decode_dispatch",
                                    "host: none", "prefill"]
    assert [round(g[1] * 1e3, 6) for g in gaps] == [35.0, 20.0, 15.0, 10.0]


def test_breakdown_sums_self_time_by_program_and_name():
    ops = dict(hand_trace().breakdown(0, 200 * MS)["device_ops"])
    assert ops["serve_prefill/fusion.1"] == pytest.approx(0.050)
    # the op nested in the other is its own: 20 ms of self time in all
    assert ops["serve_decode/fusion.2"] == pytest.approx(0.020)
    assert program_name("jit_serve_decode(4)") == "serve_decode"


def test_roofline_sums_each_call_by_its_program():
    c = ctx_for(hand_trace())
    peaks = c.peaks
    arch, plan = c.cell.config["arch"], c.cell.config["plan"]
    for name, program, ms in (("flash_attention", "serve_prefill", 30),
                              ("swiglu", "serve_decode", 20)):
        k = c.cell.kernel(name)
        flops, nbytes = k.work(**k.call(arch, plan, c.gen, program))
        least = max(flops / peaks["bf16_flops_per_s"],
                    nbytes / peaks["hbm_bytes_per_s"])
        got = c.cell.metric(f"{name}_roofline").read(c)
        assert got == pytest.approx(100 * least / (ms * 1e-3))


def test_kernel_absent_from_the_slice_reads_nothing():
    c = ctx_for(hand_trace(), "mamba2-1.3b.prompt_heavy")
    assert c.cell.metric("ssd_roofline").read(c) is None


def test_step_mfu_counts_the_slice_calls():
    tr = hand_trace()
    c = ctx_for(tr, calls=[("prefill", 2048), ("decode", 2048)])
    counter = c.cell.counter()
    arch = c.cell.config["arch"]
    flops = (counter.flops(arch, c.gen, "prefill", 2048)
             + counter.flops(arch, c.gen, "decode", 2048))
    want = 100 * flops / (0.2 * c.peaks["bf16_flops_per_s"])
    assert c.cell.metric("step_mfu").read(c) == pytest.approx(want)


def recorded():
    """A prefill and two decode steps of qwen2-7b.prompt_heavy (16 layers,
    8 rows of 2048 tokens), recorded on one TPU v5e and reduced to the
    events `from_xplane` keeps (op texts dropped)."""
    return Trace.from_json(DATA / "qwen2-7b.prompt_heavy.slice.json")


def test_recorded_trace_program_times():
    c = ctx_for(recorded())
    assert c.cell.metric("prefill_ms").read(c) == pytest.approx(1694.864596)
    assert c.cell.metric("decode_ms").read(c) == pytest.approx(
        (44.130528 + 44.123406) / 2)
    idle = c.cell.metric("device_idle_share").read(c)
    assert idle == pytest.approx(100 * (1 - 1785.112872 / 1787.94919))


def test_recorded_trace_rooflines_by_hand():
    tr = recorded()
    c = ctx_for(tr)
    flash = tr.kernel_ops(("flash_attention",), c.lo, c.hi)
    swiglu = tr.kernel_ops(("swiglu_pallas",), c.lo, c.hi)
    assert len(flash) == 16                      # one per layer, prefill
    assert len(swiglu) == 16 + 2 * 16            # prefill and two steps
    peak, bw = 197e12, 819e9
    # causal attention, 8 rows x 28 heads x 128 wide over 2048 positions
    f_flash = 4 * 8 * 28 * 128 * (2048 * 2049 // 2)
    b_flash = 2 * 8 * 2048 * 128 * (2 * 28 + 2 * 4)
    want = 16 * max(f_flash / peak, b_flash / bw) / (
        sum(e.dur for e in flash) * 1e-9)
    assert c.cell.metric("flash_attention_roofline").read(c) == \
        pytest.approx(100 * want)
    # 16384 prefill tokens, then 8 per decode step, through 3584 x 18944
    panels = 2 * 3 * 3584 * 18944
    least = 16 * max(6 * 16384 * 3584 * 18944 / peak,
                     (panels + 2 * 2 * 16384 * 3584) / bw)
    least += 32 * max(6 * 8 * 3584 * 18944 / peak,
                      (panels + 2 * 2 * 8 * 3584) / bw)
    want = least / (sum(e.dur for e in swiglu) * 1e-9)
    got = c.cell.metric("swiglu_roofline").read(c)
    assert got == pytest.approx(100 * want)
    assert 0 < got <= 100


def test_recorded_breakdown_names_the_kernels():
    tr = recorded()
    lo, hi = tr.span("slice")
    top = [name for name, _ in tr.breakdown(lo, hi)["device_ops"]]
    assert top[0] == "serve_prefill/flash_attention.7"
    assert "serve_prefill/swiglu_pallas.7" in top
    assert not any(name.split("/")[1].startswith("while") for name in top)
