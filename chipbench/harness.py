"""The chip benchmark's harness: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name, one file per piece, so that a
configuration, a traffic mix or a metric is added as files alone:

* the cell: its entry in ``BENCHMARK.json`` (configuration, traffic, chips)
  and ``chipbench/workloads/<cell>.json`` (the limits of its compared
  numbers);
* the configuration: ``chipbench/configs/<config>.json``, whose
  ``generator`` names the operand generator in ``operands.py``;
* the traffic mix: ``chipbench/traffic/<traffic>.json``, whose ``loop``
  names the driver in ``chipbench/loops/<loop>.py``;
* each metric: ``chipbench/metrics/<metric>.py``, whose ``read(record)``
  returns the value or None where the run has nothing to read.

A run: set-up (operand, plan, compile, seeded data, warm-up), then the
window (``--seconds``; with ``--trace 1`` a short window under the
profiler), then the device's peak memory, then the program's state is
freed and the plain reference decides ``correct``. The last line of stdout
is the result; the compared numbers and their limits are the last lines
of stderr and the last key of the result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import hashlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from chipbench import operands, work, xplane

HERE = Path(__file__).resolve().parent
REFUSED_ENV = ("REPRO_PALLAS_INTERPRET", "REPRO_AUTOTUNE_CACHE", "REPRO_MEASURE")
TRACE_SECONDS = 3.0
SPAN_LABELS = ("call", "step")


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.dir = self.root / "chipbench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def data(self, kind: str, name: str) -> dict:
        return json.loads((self.dir / kind / f"{name}.json").read_text())

    def loop(self, traffic: dict):
        return load_module(self.dir / "loops" / f"{traffic['loop']}.py",
                           f"chipbench_loop_{traffic['loop']}").Loop

    def metrics(self, cell: str, traced: bool) -> list:
        """The metric entries this cell reports in a run of this kind."""
        out = []
        for m in self.spec["per_layer" if traced else "end_to_end"]:
            if cell in m.get("workloads", [cell]):
                out.append(m)
        return out

    def reader(self, metric: str):
        return load_module(self.dir / "metrics" / f"{metric}.py",
                           "chipbench_metric_" + metric.replace(".", "_")).read


class Run:
    """What a loop needs of the run: the cell's pieces, the operand and the
    host spans of set-up."""

    def __init__(self, bench: Bench, cell: str):
        self.bench = bench
        self.cell = bench.cell(cell)
        self.chips = int(self.cell["chips"])
        self.config = bench.config(self.cell["config"])
        self.traffic = bench.data("traffic", self.cell["traffic"])
        self.limits = bench.data("workloads", cell)["limits"]
        self.spans: dict = {}
        self.say = say

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans[name] = self.spans.get(name, 0.0) + time.perf_counter() - t0

    def device_key(self, seed: int):
        """A JAX key from a seed of any size (the key itself takes 32 bits)."""
        import jax

        return jax.random.PRNGKey(int(np.random.default_rng([seed, 1]).integers(2**31)))

    def make_operand(self) -> operands.Graph:
        """The configuration's operand: its pattern is the configuration's
        (``pattern_seed``), not the run's. Kept in ``chipbench/.operands``
        once made, keyed by the configuration's content and the generator's
        version."""
        cfg = self.config
        key = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode()).hexdigest()[:16]
        path = (self.bench.dir / ".operands"
                / f"{self.cell['config']}-v{operands.GENERATOR_VERSION}-{key}.npz")
        g = None
        if path.is_file():
            with np.load(path) as z:
                g = operands.Graph(int(z["n"]), z["row"], z["col"], z["val"])
        if g is None:
            g = operands.make(cfg, int(cfg["pattern_seed"]))
            path.parent.mkdir(parents=True, exist_ok=True)
            tmp = path.with_suffix(f".{os.getpid()}.tmp.npz")
            np.savez(tmp, n=g.n, row=g.row, col=g.col, val=g.val)
            os.replace(tmp, path)
        if g.n % self.chips:
            raise ValueError(f"{self.chips} chips do not divide the {g.n} rows of "
                             f"{self.cell['config']}")
        return g


class WindowWatch:
    """Counts compilations, traces, fresh handle executables and autotune
    profiling runs while ``active``; each should stay 0 in a window. A
    context manager: its listeners and hooks are removed on exit."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.active = False
        self.counts = {"compiles": 0, "traces": 0, "lowerings": 0, "profiles": 0}

    def __enter__(self):
        import jax

        from repro.core import api, autotune

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        api.register_lowering_hook(self._on_lowering)
        autotune.register_profile_hook(self._on_profile)
        return self

    def __exit__(self, *exc):
        import jax

        from repro.core import api, autotune

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        api.unregister_lowering_hook(self._on_lowering)
        autotune.unregister_profile_hook(self._on_profile)

    def _on_duration(self, event, _secs, **_kw):
        if self.active and event in self.EVENTS:
            self.counts["compiles" if "backend" in event else "traces"] += 1

    def _on_lowering(self, _handle, _key):
        if self.active:
            self.counts["lowerings"] += 1

    def _on_profile(self, _info):
        if self.active:
            self.counts["profiles"] += 1


def enable_compile_cache(bench: Bench) -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(bench.dir / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int):
    """The devices of a real accelerator run, or SystemExit."""
    import jax

    for var in REFUSED_ENV:
        if os.environ.get(var):
            raise SystemExit(f"chipbench: {var} is set; the cells measure the default "
                             f"model-decided handle with compiled kernels")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU (platform {devs[0].platform!r}); nothing run")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX sees {len(devs)}")
    from repro.kernels import ops

    if ops.kernel_backend() != "pallas":
        raise SystemExit(f"chipbench: kernel backend {ops.kernel_backend()!r}, not 'pallas'")
    return devs


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def traced_window(loop, seconds: float, trace_dir: Path) -> tuple:
    """The window under the profiler, and the reduced trace."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the host spans are the harness's own
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("window"):
            host = loop.window(seconds, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    return host, xplane.reduce(trace_dir, SPAN_LABELS, outside=f"between_{loop.unit}s")


def breakdown(trace: dict, chips: int) -> dict:
    """The ten device ops that took most time (mean over the chips) and the
    ten longest idle gaps, named by what the host was doing."""
    ops: dict = {}
    gaps = []
    for d in trace["devices"].values():
        for name, s in d["op_s"].items():
            ops[name] = ops.get(name, 0.0) + s / chips
        gaps += d["gaps"]
    return {"device_ops": [[n, s] for n, s in sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[n, s] for n, s in sorted(gaps, key=lambda g: -g[1])[:10]]}


def execute(bench: Bench, cell: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, devs, peaks: dict | None = None) -> dict:
    """One run of ``cell``: returns the result object (the last line).

    ``peaks``: the chip's peaks; by default the table's row for the devices'
    kind (an error for a kind the table lacks)."""
    run = Run(bench, cell)
    run.spans["init"] = time.perf_counter() - t_start  # imports, JAX and the chips
    devs = list(devs)[: run.chips]
    with run.span("operand"):
        run.operand = run.make_operand()
    loop = bench.loop(run.traffic)(run)
    with run.span("load"):
        loop.load(seed)
    with run.span("sync"):
        # what set-up wrote (the operand and compile cache of a first run)
        # is flushed here, so that its writeback does not land in the window
        os.sync()
    counters = loop.counters()
    setup_s = time.perf_counter() - t_start
    say(f"set-up {setup_s:.3f}s: " + ", ".join(f"{k} {v:.3f}s" for k, v in run.spans.items()))
    say(f"counters: {json.dumps(counters, default=str)}")

    with WindowWatch() as watch:
        watch.active = True
        if trace:
            host, reduced = traced_window(loop, min(seconds, TRACE_SECONDS),
                                          bench.dir / ".traces" / cell)
        else:
            host, reduced = loop.window(seconds, lambda _name: contextlib.nullcontext()), None
        watch.active = False
    after = loop.counters()
    if "compiled_steps" in counters:
        watch.counts["compiles"] += after["compiled_steps"] - counters["compiled_steps"]
    say(f"in the window: {json.dumps(watch.counts)}")
    lat = np.asarray(host["latencies_s"]) * 1e3
    if lat.size:
        med = float(np.median(lat))
        say(f"window {host['window_s']:.3f}s, {lat.size} {loop.unit}s: median {med:.3f} ms, "
            f"max {lat.max():.3f} ms at #{int(lat.argmax())}, "
            f"{int((lat > 2 * med).sum())} over twice the median")
    if any(watch.counts.values()):
        raise RuntimeError(f"the window compiled, traced or profiled: {watch.counts}")

    peak = memory_peak(devs)
    say(f"memory_peak_bytes {peak} (fullest of {len(devs)} chips)")
    loop.release()
    gc.collect()
    readings = loop.readings()

    kind = devs[0].device_kind
    peaks = peaks or work.peaks(kind)
    least, bound = work.least_time_s(loop.work, peaks, run.chips)
    say(f"least time of one {loop.unit}'s work on {run.chips} chips: {least * 1e3:.4f} ms "
        f"({bound} bound; {loop.work['flops']:.4g} flops, {loop.work['bytes']:.4g} bytes)")
    record = {"cell": cell, "chips": run.chips, "unit": loop.unit,
              "setup_s": setup_s, "spans": dict(run.spans), "counters": counters,
              "work": loop.work, "peaks": peaks, "trace": reduced, **host}
    metrics = {}
    for m in bench.metrics(cell, trace):
        value = bench.reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {k: {"value": _finite(v), "limit": run.limits[k]} for k, v in readings.items()}
    correct = all(c["value"] is not None and c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": host["attempted"], "failed": host["failed"],
              "metrics": metrics, "device": device}
    if reduced is not None:
        busy = [d["busy_s"] for d in reduced["devices"].values()]
        device["busy_s"] = sum(busy) / run.chips if busy else 0.0
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = breakdown(reduced, run.chips)
    result["checks"] = checks
    for k, c in checks.items():
        say(f"check {k}: {c['value']} (limit {c['limit']})")
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one cell of the chip benchmark.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, t_start: float) -> int:
    args = parse(argv)
    bench = Bench(HERE.parent)
    chips = int(bench.cell(args.workload)["chips"])
    devs = require_chips(chips)
    say(f"device: {devs[0].platform}, {devs[0].device_kind}, {len(devs)} devices")
    say(f"compile cache: {enable_compile_cache(bench)}")
    result = execute(bench, args.workload, args.seed, args.seconds, bool(args.trace),
                     t_start=t_start, devs=devs)
    print(json.dumps(result), flush=True)
    return 0
