"""Where in the model each device op ran: the program's named scopes.

    python3 bench/xscope.py [trace dir]    # self time by scope

The model names its parts with `jax.named_scope` (`SCOPES`; PERF.md §3
maps each to the metric it serves).  XLA keeps the name stack in each
HLO instruction's `op_name` metadata, and the TPU trace keeps it in the
stat `OP_NAME_STAT` of each op's event metadata
(`jit(serve_decode)/layers/while/body/squeeze:`).  This module adds
that to what `xtrace` keeps:

- `ScopedEvent`: an `xtrace.Event` with `scope`, the op's `op_name`
  less its leading `jit(<program>)/`;
- `with_scopes(trace, path)`: the trace's ops with the scopes read from
  the same `.xplane.pb`;
- `ScopedTrace.scope_ns`: self time by scope path.  An op with no
  `op_name` takes the name stack shared by the ops it encloses in time
  on its device (the trace gives a `while` op none, though it spans its
  body), or else the scope of the innermost op that encloses it (XLA's
  own `copy.N` inside the loop).

A scope path keeps only the named-scope components, in order:
`layers/while/body/closed_call/layer/mlp/jit(swiglu_pallas)/...` is
`layers/layer/mlp`.
"""
from __future__ import annotations

import json
from dataclasses import asdict, dataclass
from pathlib import Path

from xtrace import Event, Trace, _is_device, op_name, program_name

#: the program's scopes (`repro.models.transformer`, `layers.py`)
SCOPES = ("embed", "layers", "layer", "attn", "kv_write", "mlp", "moe",
          "ssm", "rec", "head")

#: the stat of a TPU op event that holds its HLO `op_name`
OP_NAME_STAT = "tf_op"

#: what `scopes()` names the ops that lie in no scope
UNSCOPED = "(no scope)"


@dataclass
class ScopedEvent(Event):
    scope: str = ""         # ops: the HLO op_name less `jit(<program>)/`


def strip_program(name: str) -> str:
    """`jit(serve_decode)/layers/while` -> `layers/while`."""
    if name.startswith("jit(") and ")/" in name:
        return name.split(")/", 1)[1]
    return name


def scope_path(scope: str) -> tuple:
    """The named-scope components of a scope, in order."""
    return tuple(c for c in scope.split("/") if c in SCOPES)


class ScopedTrace(Trace):
    """A `Trace` whose ops are `ScopedEvent`s."""

    @classmethod
    def from_json(cls, path) -> "ScopedTrace":
        with open(path) as f:
            d = json.load(f)
        return cls(ops=[ScopedEvent(**e) for e in d["ops"]],
                   programs=[Event(**e) for e in d["programs"]],
                   host=[Event(**e) for e in d["host"]],
                   n_devices=d["n_devices"])

    def scoped_self_ns(self, lo: float, hi: float, program: str = None):
        """[(op, scope it takes, self ns)] of the ops in [lo, hi), of one
        program where `program` is given.

        Self time is the op's time less that of the ops nested in it, as
        `Trace.self_ns` counts it.  An op with no scope of its own takes,
        where it encloses ops that have one, the name stack they share
        (the trace gives a `while` op none, though it spans its body);
        else the scope of the innermost op that encloses it."""
        out = []
        for d in sorted({e.device for e in self.ops}):
            ops = sorted((e for e in self.ops
                          if e.device == d and lo <= e.start < hi),
                         key=lambda e: (e.start, -e.end))
            parent = [-1] * len(ops)
            kids = [0.0] * len(ops)
            stack: list = []
            for i, e in enumerate(ops):
                while stack and e.start >= ops[stack[-1]].end:
                    stack.pop()
                if stack:
                    parent[i] = stack[-1]
                    kids[stack[-1]] += e.dur
                stack.append(i)
            # up: an op with no scope shares its children's name stack
            stacks: list = [None] * len(ops)
            below: list = [[] for _ in ops]
            for i in reversed(range(len(ops))):
                own = ops[i].scope
                if own:
                    stacks[i] = own.rsplit("/", 1)[0] if "/" in own else ""
                elif below[i]:
                    stacks[i] = _common(below[i])
                if stacks[i] is not None and parent[i] >= 0:
                    below[parent[i]].append(stacks[i])
            # down: else the scope of the op that encloses it
            scope = [""] * len(ops)
            for i, e in enumerate(ops):
                if e.scope:
                    scope[i] = e.scope
                elif stacks[i] is not None:
                    scope[i] = stacks[i]
                elif parent[i] >= 0:
                    scope[i] = scope[parent[i]]
                if program is None or program_name(e.program) == program:
                    out.append((e, scope[i], e.dur - kids[i]))
        return out

    def scope_ns(self, lo: float, hi: float, program: str = None) -> dict:
        """Self time in [lo, hi) summed by scope path (tuple of names)."""
        out: dict = {}
        for _, scope, ns in self.scoped_self_ns(lo, hi, program):
            key = scope_path(scope)
            out[key] = out.get(key, 0.0) + ns
        return out

    def scopes(self, lo: float, hi: float, n: int = 10) -> list:
        """The `n` scopes with most self time in [lo, hi), as
        [`program/scope path`, seconds]."""
        out: dict = {}
        for op, scope, ns in self.scoped_self_ns(lo, hi):
            path = "/".join(scope_path(scope)) or UNSCOPED
            key = f"{program_name(op.program)}/{path}"
            out[key] = out.get(key, 0.0) + ns
        top = sorted(out.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]


def _common(stacks: list) -> str:
    """The leading components that name stacks share."""
    parts = [s.split("/") for s in stacks]
    n = 0
    while all(len(p) > n and p[n] == parts[0][n] for p in parts):
        n += 1
    return "/".join(parts[0][:n])


# -- the op names in an .xplane.pb -----------------------------------------
#
# `jax.profiler.ProfileData` gives an op event's own stats, not those of
# its metadata, where the TPU trace keeps `OP_NAME_STAT`.  So the planes'
# event metadata are read here from the serialized `XSpace` (tsl
# `xplane.proto`: XSpace.planes 1; XPlane.name 2, event_metadata 4,
# stat_metadata 5; XEventMetadata.name 2, display_name 4, stats 5;
# XStatMetadata.id 1, name 2; XStat.metadata_id 1, uint64 3, int64 4,
# str 5, ref 7).


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field, value) of the protobuf message in buf[lo:hi]; the value of
    a length-delimited field is its (start, end) in buf."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stats(buf, span, names: dict) -> dict:
    """{stat name: value} of an XEventMetadata's stats."""
    out = {}
    for f, v in _fields(buf, *span):
        if f != 5:
            continue
        sid, val = None, None
        for g, w in _fields(buf, *v):
            if g == 1:
                sid = w
            elif g in (3, 4):
                val = w
            elif g == 5:
                val = _text(buf, w)
            elif g == 7:
                val = names.get(w)
        out[names.get(sid)] = val
    return out


def read_op_names(path) -> dict:
    """{(program id, op name): op_name} of the TPU ops of one `.xplane.pb`
    that carry an op_name."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 4:
                metas += [w for k, w in _fields(buf, *v) if k == 2]
            elif g == 5:
                entry = dict(_fields(buf, *v))
                if 2 in entry:
                    meta = dict(_fields(buf, *entry[2]))
                    stat_names[meta.get(1)] = _text(buf, meta[2])
        if not _is_device(name):
            continue
        for span in metas:
            meta = dict(_fields(buf, *span))
            stats = _stats(buf, span, stat_names)
            tf_op, pid = stats.get(OP_NAME_STAT), stats.get("program_id")
            if tf_op and pid is not None:
                op = (_text(buf, meta[4]) if 4 in meta
                      else op_name(_text(buf, meta[2])))
                out[(pid, op)] = tf_op
    return out


def program_id(module: str):
    """`jit_serve_decode(7668439845386167065)` -> 7668439845386167065."""
    if module.endswith(")") and "(" in module:
        text = module[module.rindex("(") + 1:-1]
        if text.isdigit():
            return int(text)
    return None


def with_scopes(trace: Trace, path) -> ScopedTrace:
    """The trace, with each op's scope read from the `.xplane.pb` it was
    read from (`xtrace.from_xplane`)."""
    names = read_op_names(path)
    ops = [ScopedEvent(**asdict(e), scope=strip_program(names.get(
        (program_id(e.program), e.name), "").rstrip(":")))
        for e in trace.ops]
    return ScopedTrace(ops=ops, programs=trace.programs, host=trace.host,
                       n_devices=trace.n_devices)


def main(argv=None) -> int:
    """Print the scopes with most self time in the slice of a `--trace 1`
    run's trace, and the ops of each program that lie in no scope."""
    import argparse

    from harness import SPANS
    from run import TRACE_DIR
    from xtrace import find_xplane, from_xplane
    ap = argparse.ArgumentParser(description="self time by scope")
    ap.add_argument("trace_dir", nargs="?", default=str(TRACE_DIR))
    ap.add_argument("-n", type=int, default=10)
    args = ap.parse_args(argv)
    path = find_xplane(args.trace_dir)
    tr = with_scopes(from_xplane(path, SPANS), path)
    lo, hi = tr.span("slice")
    for key, s in tr.scopes(lo, hi, args.n):
        print(f"{s:12.6f} s  {key}")
    unscoped: dict = {}
    for op, scope, ns in tr.scoped_self_ns(lo, hi):
        if not scope_path(scope):
            key = f"{program_name(op.program)}/{op.name}"
            unscoped[key] = unscoped.get(key, 0.0) + ns
    print("ops in no scope:")
    for key, ns in sorted(unscoped.items(), key=lambda kv: -kv[1])[:args.n]:
        print(f"{ns * 1e-9:12.6f} s  {key}")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
