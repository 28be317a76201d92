"""The benchmark's data model: BENCHMARK.json, and the configurations,
traffic mixes and per-layer metric readers it names, found by name.

Nothing here imports JAX or the program, so the tests can load every piece
of the benchmark in a fraction of a second.

  configs/<name>.json       one deployment: sizes, walk model, guarantees,
                            and the name of its plain reference module
                            (configs/<reference>.py)
  traffic/<name>.json       one traffic mix: the parameters the general
                            generator (traffic.py) reads
  metrics/<name>.py         one per-layer metric: `read(ctx)` returns a
                            number, or None where the run has nothing for
                            it to read

A later cell, configuration, mix or metric is new files plus new entries in
BENCHMARK.json; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import math
from pathlib import Path
from typing import Callable, Iterable, Optional

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def load_spec(path: Optional[Path] = None) -> dict:
    """BENCHMARK.json (default: the one at the checkout's root)."""
    path = Path(path) if path else CHECKOUT / "BENCHMARK.json"
    with open(path) as f:
        return json.load(f)


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                   f"{[w['name'] for w in spec['workloads']]}")


def config_entry(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    with open(path) as f:
        return json.load(f)


def load_config(spec: dict, name: str, base: Path = HERE) -> dict:
    """configs/<name>.json, which BENCHMARK.json's entry must name as its
    `file` (relative to the checkout, whose benchmark directory is
    `base`)."""
    entry = config_entry(spec, name)
    path = Path(base) / "configs" / f"{name}.json"
    rel = path.relative_to(Path(base).parents[1]).as_posix()
    if entry["file"] != rel:
        raise ValueError(f"configuration {name!r}: BENCHMARK.json names "
                         f"{entry['file']!r}, its file is {rel!r}")
    cfg = _json(path)
    if cfg.get("name", name) != name:
        raise ValueError(f"{rel} names itself {cfg['name']!r}")
    return cfg


def load_traffic(name: str, base: Path = HERE) -> dict:
    mix = _json(Path(base) / "traffic" / f"{name}.json")
    if mix.get("name", name) != name:
        raise ValueError(f"traffic/{name}.json names itself {mix['name']!r}")
    return mix


def _load_module(path: Path, label: str):
    if not path.is_file():
        raise FileNotFoundError(f"benchmark module missing: {path}")
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(cfg: dict, base: Path = HERE):
    """The plain reference module a configuration names."""
    name = cfg["reference"]
    return _load_module(Path(base) / "configs" / f"{name}.py",
                        f"bench_reference_{name}")


def load_reader(name: str, base: Path = HERE) -> Callable[[dict], object]:
    """metrics/<name>.py's `read(ctx)`."""
    mod = _load_module(Path(base) / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))
    return mod.read


def end_to_end_for(spec: dict, cell: str) -> list:
    """The end-to-end metrics a cell reports: those without a `workloads`
    list, and those that list it."""
    return [m for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_for(spec: dict, cell: str) -> list:
    """The per-layer metrics a cell reports: those that list it, and those
    without a list whose `moves` metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(spec, cell)}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


# ------------------------------------------------------------ arithmetic


def closed_loop_rate(t0: float, completions: Iterable[tuple]) -> float:
    """Items per second of a closed loop: every item of every completed
    request, over all the time from the window's start to the completion
    of the last one. `completions` holds (completion time, items)."""
    done = list(completions)
    if not done:
        raise ValueError("no request completed in the window")
    return sum(n for _, n in done) / (max(t for t, _ in done) - t0)


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]): the smallest value with at
    least q% of the sample at or below it. Every sample counts; no
    interpolation between two of them."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no samples")
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def open_loop_latencies(due: Iterable[float], done: Iterable[Optional[float]],
                        give_up: float) -> list:
    """Latency of each request from its due time to its result; a request
    with no result (failed) counts as waiting until `give_up`."""
    return [(d if d is not None else give_up) - u for u, d in zip(due, done)]


# ----------------------------------------------------------------- output


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: dict, breakdown: Optional[dict] = None
                ) -> str:
    """The run's last line of standard output. `checks` (each number
    compared, beside its limit) comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def check_lines(checks: dict) -> list:
    """One plain line per compared number: `name value <= limit`."""
    return [f"check {k} {v['value']} <= {v['limit']}"
            f"{'' if v['value'] <= v['limit'] else '  FAILED'}"
            for k, v in checks.items()]
