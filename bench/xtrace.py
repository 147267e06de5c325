"""Reduction of a `jax.profiler` trace to the numbers the metrics read.

`from_xplane` keeps three kinds of events from an `.xplane.pb`, on the
trace's own clock in nanoseconds:

- device ops: the "XLA Ops" line of each TPU plane, each tagged with the
  program ("XLA Modules" event) it ran in;
- device programs: the "XLA Modules" line;
- host spans: the benchmark's own `TraceAnnotation`s on the host plane.

`Trace` holds them as plain lists, so a small recorded trace can be kept
as JSON and the reductions tested on it without a chip.
"""
from __future__ import annotations

import bisect
import json
from dataclasses import asdict, dataclass, field
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Event:
    name: str
    start: float            # ns on the trace's clock
    end: float
    device: int = 0
    program: str = ""       # ops: the program they ran in
    detail: str = ""        # ops: the HLO instruction as the trace gives it

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: list = field(default_factory=list)
    programs: list = field(default_factory=list)
    host: list = field(default_factory=list)
    n_devices: int = 0

    # -- persistence -------------------------------------------------------

    def to_json(self, path) -> None:
        with open(path, "w") as f:
            json.dump({"n_devices": self.n_devices,
                       "ops": [asdict(e) for e in self.ops],
                       "programs": [asdict(e) for e in self.programs],
                       "host": [asdict(e) for e in self.host]}, f)

    @classmethod
    def from_json(cls, path) -> "Trace":
        with open(path) as f:
            d = json.load(f)
        return cls(ops=[Event(**e) for e in d["ops"]],
                   programs=[Event(**e) for e in d["programs"]],
                   host=[Event(**e) for e in d["host"]],
                   n_devices=d["n_devices"])

    # -- host spans ----------------------------------------------------------

    def span(self, name: str):
        """(start, end) of the first host span of that name, or None."""
        for e in self.host:
            if e.name == name:
                return e.start, e.end
        return None

    def host_label(self, t: float) -> str:
        """The innermost host span open at time t, or "host: none"."""
        best = None
        for e in self.host:
            if e.start <= t < e.end and e.name != "slice":
                if best is None or e.dur < best.dur:
                    best = e
        return best.name if best else "host: none"

    # -- device --------------------------------------------------------------

    def program_calls(self, program: str, lo: float, hi: float) -> list:
        """Program events whose name is that jitted function's, in [lo, hi)."""
        return [e for e in self.programs
                if program_name(e.name) == program and lo <= e.start < hi]

    def kernel_ops(self, names, lo: float, hi: float) -> list:
        """Ops in [lo, hi) of a Pallas kernel called through one of the
        jitted functions `names`: the custom call's HLO instruction is
        named after that function (`flash_attention.7`)."""
        return [e for e in self.ops if lo <= e.start < hi
                and e.name.split(".")[0] in names]

    def busy_intervals(self, device: int, lo: float, hi: float) -> list:
        """Union of the device's op intervals, clipped to [lo, hi)."""
        iv = sorted((max(e.start, lo), min(e.end, hi)) for e in self.ops
                    if e.device == device and e.end > lo and e.start < hi)
        out = []
        for s, t in iv:
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], t)
            else:
                out.append([s, t])
        return out

    def busy_ns(self, lo: float, hi: float) -> float:
        """Busy time in [lo, hi), averaged over the devices."""
        devices = sorted({e.device for e in self.ops}) or [0]
        total = sum(t - s for d in devices
                    for s, t in self.busy_intervals(d, lo, hi))
        return total / len(devices)

    def idle_gaps(self, lo: float, hi: float, device: int = 0) -> list:
        """(start, end) of every stretch in [lo, hi) with no op running."""
        gaps, t = [], lo
        for s, e in self.busy_intervals(device, lo, hi):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        return gaps

    def self_ns(self, lo: float, hi: float) -> dict:
        """Device time of each op in [lo, hi) less the time of the ops
        nested in it (a `while` op spans its loop's body), summed by
        program and op name."""
        out: dict = {}
        for d in sorted({e.device for e in self.ops}):
            ops = sorted((e for e in self.ops
                          if e.device == d and lo <= e.start < hi),
                         key=lambda e: (e.start, -e.end))
            stack: list = []            # [event, time of its children]
            for e in ops + [None]:
                while stack and (e is None or e.start >= stack[-1][0].end):
                    done, kids = stack.pop()
                    key = f"{program_name(done.program)}/{done.name}"
                    out[key] = out.get(key, 0.0) + done.dur - kids
                    if stack:
                        stack[-1][1] += done.dur
                if e is not None:
                    stack.append([e, 0.0])
        return out

    def breakdown(self, lo: float, hi: float, n: int = 10) -> dict:
        """The ops that took most device time (self time, by program and
        name) and the longest idle gaps, named by what the host was doing."""
        top = sorted(self.self_ns(lo, hi).items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_gaps(lo, hi), key=lambda g: g[0] - g[1])[:n]
        return {"device_ops": [[k, v * 1e-9] for k, v in top],
                "idle_gaps": [[self.host_label((s + t) / 2), (t - s) * 1e-9]
                              for s, t in gaps]}


def program_name(module: str) -> str:
    """`jit_serve_decode(12)` or `jit_serve_decode` -> `serve_decode`."""
    base = module.split("(")[0]
    return base[4:] if base.startswith("jit_") else base


def op_name(text: str) -> str:
    """`%fusion.12 = bf16[8]{0} fusion(...)` -> `fusion.12`: a TPU trace
    names each op by its HLO instruction's text."""
    return text.split(" = ", 1)[0].lstrip("%")


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") and plane_name[
        len("/device:TPU:"):].isdigit()


def from_xplane(path, host_spans=()) -> Trace:
    """Read the device and host events of one `.xplane.pb`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    tr = Trace()
    devices = []
    for plane in pd.planes:
        if _is_device(plane.name):
            dev = int(plane.name[len("/device:TPU:"):])
            devices.append(dev)
            mods, ops = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods = [Event(e.name, e.start_ns, e.end_ns, dev)
                            for e in line.events]
                elif line.name == OPS_LINE:
                    ops = [Event(op_name(e.name), e.start_ns, e.end_ns, dev,
                                 detail=e.name)
                           for e in line.events]
            mods.sort(key=lambda e: e.start)
            starts = [m.start for m in mods]
            for op in ops:
                i = bisect.bisect_right(starts, op.start) - 1
                if i >= 0 and op.start < mods[i].end:
                    op.program = mods[i].name
            tr.programs += mods
            tr.ops += ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                tr.host += [Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.name in host_spans]
    tr.n_devices = len(devices)
    return tr


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]
