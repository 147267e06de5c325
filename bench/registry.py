"""Finds every piece of the benchmark by the name `BENCHMARK.json` gives it.

A cell names a configuration and a traffic mix; each lives in a file of
its own, and so do each per-layer metric, each kernel's work count, each
reference and each limit.  Adding a cell, a configuration, a mix or a
metric therefore adds files and entries and edits none:

    bench/configs/<config>.json      sizes, plan, reference and counter names
    bench/traffic/<mix>.json         loop kind and its parameters
    bench/metrics/<metric>.py        one per-layer metric's reduction
    bench/kernels/<kernel>.py        a kernel's FLOPs and bytes per call
    bench/reference/<family>.py      plain float32 forward pass + weights
    bench/counters/<family>.py       model FLOPs per program call
    bench/limits/<cell>.json         limits of the numbers `correct` compares

Adding a configuration of a new family, with its cell:

1. `bench/configs/<config>.json`: the published `config.json` keys, the
   `reduced` cuts, `reference` and `counter` (family names), `arch` and
   `plan` as the program's `ArchConfig` and `PlanConfig` spell them (a
   nested section such as `moe` as a mapping, a tuple as a list;
   `harness.from_json` turns them back).
2. `bench/reference/<family>.py`: `make_params(config, key)`,
   `last_logits(config, params, tokens, n_last, mode)`,
   `published_sizes(f) -> dict`, which maps each `arch` key to the value
   the configuration's published keys require (the spec test compares),
   and `SMALL_ARCH`, the `arch` widths at which the CPU tests check the
   reference against the program's forward pass.
3. `bench/counters/<family>.py`: `flops(arch, gen, program, pos)`.
4. For a new kernel, `bench/kernels/<kernel>.py` (`TRACE_NAMES`, `work`,
   `call`) and `bench/metrics/<kernel>_roofline.py`; for what the family
   adds, a metric of its own under `bench/metrics/`, which may read the
   program's `repro.obs` counters and gauges from `ctx.counters`.
5. `bench/limits/<cell>.json`, from the readings of `bench/calibrate.py`.
6. In `BENCHMARK.json`: an entry under `configs`, one under `workloads`,
   and the new cell's name appended to the `workloads` list of each
   metric that lists its cells and that the cell reports.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a benchmark file by path (as `bench_<dir>_<stem>`)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    name = f"bench_{path.parent.name}_{path.stem}".replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    """One workload of `BENCHMARK.json` with everything it names loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # the end-to-end metric entries this cell reports
    per_layer: list         # the per-layer metric entries this cell reports
    bench_dir: Path

    def reference(self) -> ModuleType:
        return load_module(self.bench_dir / "reference"
                           / f"{self.config['reference']}.py")

    def counter(self) -> ModuleType:
        return load_module(self.bench_dir / "counters"
                           / f"{self.config['counter']}.py")

    def metric(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "metrics" / f"{name}.py")

    def kernel(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "kernels" / f"{name}.py")

    def limits(self) -> dict:
        return load_json(self.bench_dir / "limits" / f"{self.name}.json")


def load_peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The chip's peaks; a kind the table lacks is an error, not a default."""
    table = load_json(bench_dir / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {sorted(table)}")
    return table[device_kind]


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    bench_dir = root / "bench"
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
        bench_dir=bench_dir)
