"""The program's named scopes on the device trace (`bench/xscope.py`) and
the metric that reads them, on hand-made traces worked by hand."""
from pathlib import Path

import pytest

import run
import traffic
from registry import load_cell, load_peaks
from xscope import (UNSCOPED, ScopedEvent, ScopedTrace, scope_path,
                    strip_program)
from xtrace import Event

MS = 1e6        # the trace's clock counts nanoseconds
DATA = Path(__file__).resolve().parent / "data"
DEC = "jit_serve_decode(4)"
PRE = "jit_serve_prefill(3)"
SWIGLU = ("jit(serve_decode)/layers/while/body/closed_call/layer/mlp/"
          "jit(swiglu_pallas)/swiglu_pallas/pallas_call")


def test_scope_path_keeps_the_named_scopes_only():
    assert strip_program("jit(serve_decode)/layers/while/body/squeeze") == \
        "layers/while/body/squeeze"
    assert strip_program("") == ""
    assert scope_path(strip_program(SWIGLU)) == ("layers", "layer", "mlp")
    assert scope_path("layers/while/body/squeeze") == ("layers",)
    # whole components: `layers` is not `layer`, `kv_write` not `kv`
    assert scope_path("layers_x/layer2/kv/attn") == ("attn",)
    assert scope_path("reduce") == ()


def op(name, a, b, scope="", program=DEC):
    return ScopedEvent(name, a * MS, b * MS, 0, program, scope=scope)


def hand_trace() -> ScopedTrace:
    """Two decode steps of a two-layer scan.  In each: the `while` op of
    the scan (20 ms of its own; as on the chip, the trace gives it no
    op_name), inside it per layer a weight slice (`layers`, 5 ms), XLA's
    copy with no op_name (4 ms), the kernel (`layers/layer/mlp`, 10 ms)
    and the cache write (`layers/layer/attn/kv_write`, 1 ms); after it
    the argmax with no scope (3 ms)."""
    ops = [op("fusion.0", 0, 2, "embed/gather", PRE)]
    for t0 in (100, 200):
        ops.append(op("while.1", t0, t0 + 60))
        for t in (t0 + 5, t0 + 30):
            ops += [op("dynamic-slice_bitcast_fusion.9", t, t + 5,
                       "layers/while/body/squeeze"),
                    op("copy.17", t + 5, t + 9),
                    op("swiglu_pallas.7", t + 9, t + 19, strip_program(SWIGLU)),
                    op("dynamic-update-slice_fusion.1", t + 19, t + 20,
                       "layers/while/body/closed_call/layer/attn/kv_write/"
                       "dynamic_update_slice")]
        ops.append(op("iota_reduce_fusion", t0 + 60, t0 + 63))
    return ScopedTrace(
        ops=ops,
        programs=[Event(PRE, 0, 2 * MS, 0), Event(DEC, 100 * MS, 163 * MS),
                  Event(DEC, 200 * MS, 263 * MS)],
        host=[Event("slice", 0, 300 * MS)], n_devices=1)


def test_an_op_without_op_name_takes_its_enclosing_scope():
    tr = hand_trace()
    taken = {o.name: scope for o, scope, _ in
             tr.scoped_self_ns(0, 300 * MS, "serve_decode")}
    # the loop: the name stack its body's ops share; the copy: the loop's
    assert taken["while.1"] == "layers/while/body"
    assert taken["copy.17"] == "layers/while/body"
    assert taken["iota_reduce_fusion"] == ""        # nothing encloses it
    # an op that has a scope keeps its own
    assert taken["swiglu_pallas.7"] == strip_program(SWIGLU)


def test_self_time_by_scope_path():
    by = hand_trace().scope_ns(0, 300 * MS, "serve_decode")
    # per step: the while op's own 60 - 2 x 20 = 20, plus 2 x (5 + 4)
    assert by[("layers",)] == pytest.approx(2 * (20 + 18) * MS)
    assert by[("layers", "layer", "mlp")] == pytest.approx(2 * 20 * MS)
    assert by[("layers", "layer", "attn", "kv_write")] == \
        pytest.approx(2 * 2 * MS)
    assert by[()] == pytest.approx(2 * 3 * MS)
    assert ("embed",) not in by                     # the prefill's


def test_scopes_list_names_program_and_path():
    top = hand_trace().scopes(0, 300 * MS)
    assert top[0] == ["serve_decode/layers", pytest.approx(0.076)]
    assert [k for k, _ in top] == [
        "serve_decode/layers", "serve_decode/layers/layer/mlp",
        f"serve_decode/{UNSCOPED}", "serve_decode/layers/layer/attn/kv_write",
        "serve_prefill/embed"]
    assert len(hand_trace().scopes(0, 300 * MS, n=2)) == 2


def ctx_for(trace, cell_name="qwen2-7b.decode_heavy"):
    cell = load_cell(cell_name)
    gen = traffic.generator(cell.traffic, 100, 0)
    lo, hi = trace.span("slice")
    return run.MetricContext(trace=trace, lo=lo, hi=hi, cell=cell, gen=gen,
                             calls=[], peaks=load_peaks("TPU v5 lite"))


def test_layer_stack_ms_by_hand():
    c = ctx_for(hand_trace())
    # per step: the while op's own 20 ms and two slices and copies of 9
    got = c.cell.metric("layer_stack_ms").read(c)
    assert got == pytest.approx(20 + 2 * 9)
    assert got <= c.cell.metric("decode_ms").read(c)


def test_layer_stack_ms_reads_nothing_from_an_unscoped_program():
    tr = hand_trace()
    for o in tr.ops:
        o.scope = "while/body/squeeze" if o.scope else ""
    c = ctx_for(tr)
    assert c.cell.metric("layer_stack_ms").read(c) is None


def test_scoped_trace_round_trips_through_json(tmp_path):
    tr = hand_trace()
    tr.to_json(tmp_path / "slice.json")
    back = ScopedTrace.from_json(tmp_path / "slice.json")
    assert back.ops == tr.ops and back.programs == tr.programs


def recorded():
    """Four decode steps of qwen2-7b.decode_heavy (20 layers, 32 rows),
    recorded on one TPU v5e, with each op's scope as `with_scopes` read it
    from the trace (op texts dropped)."""
    return ScopedTrace.from_json(DATA / "qwen2-7b.decode_heavy.slice.json")


def test_recorded_layer_stack_ms_by_hand():
    tr = recorded()
    c = ctx_for(tr)
    calls = tr.program_calls("serve_decode", c.lo, c.hi)
    assert len(calls) == 4
    # by hand: the scan's `while` op spans the 20 layers; what it spends
    # outside the ops nested in it that sit in a `layer` is the stack's
    want = 0.0
    for call in calls:
        ops = [o for o in tr.ops if call.start <= o.start < call.end]
        (loop,) = [o for o in ops if o.name.startswith("while")]
        inner = [o for o in ops if o is not loop
                 and loop.start <= o.start < loop.end]
        want += loop.dur - sum(o.dur for o in inner
                               if "layer" in scope_path(o.scope))
        want += sum(o.dur for o in ops if o not in inner and o is not loop
                    and scope_path(o.scope) == ("layers",))
    got = c.cell.metric("layer_stack_ms").read(c)
    assert got == pytest.approx(want / 4 * 1e-6)
    assert got == pytest.approx(27.56056675)
    assert got < c.cell.metric("decode_ms").read(c)


def test_recorded_scopes_name_the_stack_first():
    tr = recorded()
    lo, hi = tr.span("slice")
    top = [k for k, _ in tr.scopes(lo, hi)]
    assert top[:2] == ["serve_decode/layers", "serve_decode/layers/layer/mlp"]
    # the weight slices feeding the kernel sit in `layers`, the kernel in
    # `layer/mlp`
    by_op = {o.name: scope_path(s) for o, s, _ in tr.scoped_self_ns(lo, hi)}
    assert by_op["dynamic-slice_bitcast_fusion.10"] == ("layers",)
    assert by_op["swiglu_pallas.7"] == ("layers", "layer", "mlp")


# -- reading op names from a serialized XSpace -------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    """One protobuf field: an int as a varint, str or bytes as length-
    delimited."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(data)) + data


def _plane(name: str, stat_names: dict, metas: list) -> bytes:
    """An XPlane: stat metadata {id: name}; event metadata as
    (id, name, display name, [(stat id, value)])."""
    out = _field(2, name)
    for mid, text, display, stats in metas:
        body = _field(1, mid) + _field(2, text) + _field(4, display)
        for sid, v in stats:
            body += _field(5, _field(1, sid) + _field(3 if isinstance(
                v, int) else 5, v))
        out += _field(4, _field(1, mid) + _field(2, body))
    for sid, sname in stat_names.items():
        out += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                 + _field(2, sname)))
    return out


def test_op_names_read_from_event_metadata(tmp_path):
    from jax.profiler import ProfileData

    from xscope import program_id, read_op_names, with_scopes
    from xtrace import Trace
    stat_names = {7: "program_id", 9: "tf_op", 11: "flops"}
    pid = 2 ** 63 + 5               # program ids are unsigned 64-bit
    device = _plane("/device:TPU:0", stat_names, [
        (1, "%fusion.3 = bf16[8] fusion(%p)", "fusion.3",
         [(11, 0), (7, pid), (9, "jit(serve_decode)/layers/while/body/"
                                 "squeeze:")]),
        (2, "%copy.1 = bf16[8] copy(%fusion.3)", "copy.1", [(7, pid)])])
    host = _plane("/host:CPU", stat_names, [
        (1, "fusion.3", "fusion.3", [(7, pid), (9, "host op")])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    # the bytes are an XSpace as JAX's own reader takes it
    planes = ProfileData.from_serialized_xspace(path.read_bytes()).planes
    assert [p.name for p in planes] == ["/device:TPU:0", "/host:CPU"]

    assert read_op_names(path) == {
        (pid, "fusion.3"): "jit(serve_decode)/layers/while/body/squeeze:"}
    program = f"jit_serve_decode({pid})"
    assert program_id(program) == pid and program_id("jit_f") is None
    tr = Trace(ops=[Event("fusion.3", 0, 1, 0, program),
                    Event("copy.1", 1, 2, 0, program),
                    Event("fusion.3", 2, 3, 0, "jit_serve_prefill(1)")])
    got = with_scopes(tr, path)
    assert [o.scope for o in got.ops] == ["layers/while/body/squeeze", "",
                                          ""]
