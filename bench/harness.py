"""The benchmark harness: finds a cell's configuration, traffic, driver and
metric readers by name, runs set-up, the timed window and the check, and
prints the result line.

Layout, all found by the names in ``BENCHMARK.json``:

  bench/configs/<config>.json    graph generator, layout, deployment
  bench/traffic/<traffic>.json   ``driver`` name, its parameters and limits
  bench/drivers/<driver>.py      ``Driver(ctx)`` with ``setup()``,
                                 ``window(seconds)`` and ``check()``
  bench/metrics/<metric>.py      ``read(ctx)`` -> number or None
  bench/peaks.json               published peaks by ``device_kind``
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path

from bench import graphs, tracing

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load_module(path: Path, tag: str):
    if not path.exists():
        raise FileNotFoundError(f"no {tag} file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{tag}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names."""

    def __init__(self, root: Path = ROOT, bench_dir: Path | None = None):
        self.root = Path(root)
        self.dir = Path(bench_dir) if bench_dir is not None else self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for c in self.spec["workloads"]:
            if c["name"] == name:
                return c
        raise ValueError(f"unknown workload {name!r}")

    def config_file(self, name: str) -> Path:
        return self.dir / "configs" / f"{name}.json"

    def config(self, name: str) -> dict:
        return json.loads(self.config_file(name).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def driver(self, name: str):
        return _load_module(self.dir / "drivers" / f"{name}.py", "driver")

    def reader(self, metric: str):
        return _load_module(self.dir / "metrics" / f"{metric}.py", "metric").read

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())["devices"]
        if device_kind not in table:
            raise KeyError(f"no published peaks for device kind {device_kind!r}")
        return table[device_kind]

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.spec["end_to_end"] if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list[dict]:
        """The cell's per-layer metrics: those that list it, and those that
        list no cells and move an end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [
            m for m in self.spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e)
        ]


@dataclasses.dataclass
class Context:
    """What a driver and a metric reader see of one run."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    graph: graphs.Graph
    spans: tracing.Spans
    peak: dict | None = None
    window: dict | None = None  # the driver's window result
    trace: dict | None = None  # reduce_events of the traced window


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_checkout_compile_cache(root: Path):
    """JAX's persistent compile cache at a fixed path in the checkout, and
    every program cached, so only a checkout's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_record(devices, chips: int) -> dict:
    used = devices[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used]
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def run_cell(args, t_start: float, bench: Bench | None = None,
             require_tpu: bool = True, cache_dir: Path = graphs.CACHE_DIR) -> int:
    """One run of one cell; prints the result line and returns the exit code."""
    import jax

    bench = bench if bench is not None else Bench()
    cell = bench.cell(args.workload)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(f"needs {cell['chips']} TPU chip(s); JAX sees {devices}", file=sys.stderr)
        return 2
    use_checkout_compile_cache(bench.root)
    traffic = bench.traffic(cell["traffic"])
    config = bench.config(cell["config"])
    spans = tracing.Spans()
    with spans.span("load_graph"):
        graph = graphs.load_or_build(bench.config_file(cell["config"]), config,
                                     cache_dir=cache_dir)
    ctx = Context(cell=cell, config=config, traffic=traffic, seed=args.seed,
                  graph=graph, spans=spans)
    driver = bench.driver(traffic["driver"]).Driver(ctx)
    with spans.span("warm_up"):
        driver.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = str(cache_dir.parent / "trace" / args.workload)
    if args.trace:
        spans.annotate = True
        tracing.start_trace(trace_dir)
    with spans.span("window"):
        ctx.window = driver.window(args.seconds)
    if args.trace:
        tracing.stop_trace()
        spans.annotate = False
    device = device_record(devices, cell["chips"])

    checks = driver.check()
    correct = all(v <= lim for _, v, lim in checks)

    out = {"correct": correct, "attempted": ctx.window["attempted"],
           "failed": ctx.window["failed"]}
    if args.trace:
        ctx.trace = tracing.reduce_events(tracing.extract(trace_dir))
        ctx.peak = bench.peaks(device["kind"]) if require_tpu else None
        device.update(busy_s=ctx.trace["busy_s"], window_s=ctx.trace["window_s"])
        metrics = {}
        for m in bench.per_layer(cell["name"]):
            value = bench.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = tracing.breakdown(ctx.trace)
    else:
        values = dict(ctx.window["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench.end_to_end(cell["name"])}
    out.update(metrics=metrics, device=device)
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}

    print(f"setup_s {setup_s:.3f} (graph cache {'hit' if graph.cache_hit else 'built'},"
          f" build {graph.build_s:.1f} s)", file=sys.stderr)
    for n, v, lim in checks:
        print(f"check {n}: {v} (limit {lim})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
