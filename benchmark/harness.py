"""One run of one cell, as the driver's contract lays it out.

A driver process that never starts a JAX backend: it finds the cell in
``BENCHMARK.json``, its configuration, traffic and kind through
``manifest.Benchmark``, calls ``tpu_air.init()``, lets the kind drive the job
through the program's normal entry point on leased workers, reads every
metric through its reader, checks, prints one last line and exits.  No chip,
or fewer than the cell asks for, or a worker that computed anywhere but on a
TPU of that count: exit non-zero, no result line.

``--rehearse`` runs the same control flow on the CPU at ``T5Config.tiny()``
with the traffic file's ``rehearse`` numbers.  It exists for the tests; it
prints no line a driver could read as a result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from . import manifest as _manifest
from . import peaks, xplane

TRACE_DIR = ".bench_traces"  # in the checkout, git-ignored


class RunFailure(RuntimeError):
    """The run cannot give a result (as opposed to a wrong one)."""


@dataclass
class Context:
    """What a kind is given, and where it leaves what it saw."""

    bench: _manifest.Benchmark
    cell: Dict[str, Any]
    cfg: Dict[str, Any]          # the configuration file's dict
    traffic: Dict[str, Any]      # the traffic file's dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    scratch: str                 # under TMPDIR, removed after the run
    trace_dir: str               # under the checkout, git-ignored
    facts: Dict[str, Any] = field(default_factory=dict)
    problems: List[str] = field(default_factory=list)
    notes: Dict[str, Any] = field(default_factory=dict)
    window_s: Optional[float] = None
    window_start: Optional[float] = None  # time.time() at its first instant
    attempted: int = 0
    failed: int = 0

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.problems.append(what)


@dataclass
class ReadContext:
    """What a metric's reader is given."""

    facts: Dict[str, Any]
    trace: Optional[xplane.TraceSummary]
    cfg: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    peak: Optional[Dict[str, float]]


def place_compile_cache() -> str:
    """The benchmark gives the program its compile cache: ``.jax_cache/`` in
    the checkout, a fixed path (the path is part of the cache's key), with
    no size cap.  A machine that names a shared, capped directory (192 MiB
    where these runs were made) cannot hold the fine-tune cells' 158 MB
    train step beside anything else: every run then compiles for five
    minutes.  The program keeps its cache where the environment says, so
    setting the environment before ``tpu_air.init()`` is all it takes."""
    path = os.path.join(_manifest.REPO, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    jax = sys.modules.get("jax")
    if jax is not None:
        # imported already: it read the environment then, and the workers
        # are forked from this process with its configuration
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def _say(**line: Any) -> None:
    """An earlier line of output: information, not the result."""
    print(json.dumps({"info": line}), flush=True)


def chips_here() -> int:
    from tpu_air.core import chips

    return chips.local_chip_count() if chips.accelerator_expected() else 0


def _read_metrics(bench: _manifest.Benchmark, group: str, ctx: Context,
                  rc: ReadContext) -> Dict[str, Dict[str, Any]]:
    """Every metric of ``group`` the manifest gives this cell: its reader is
    given the facts and the trace; one that finds nothing returns None and
    the metric is left out."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in bench.metrics(group, ctx.cell["name"]):
        reader = bench.module("readers", m["reader"])
        value = reader.read(rc, **m.get("args", {}))
        if value is None:
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _device(ctx: Context, reports: List[Dict[str, Any]],
            platform: str) -> Dict[str, Any]:
    """The device as the leased workers saw it (``Runtime.device_reports``);
    refuses anything but ``platform`` with the cell's chip count."""
    if not reports:
        raise RunFailure("no leased worker reported a device")
    for w in reports:
        if w["platform"] != platform:
            raise RunFailure(
                f"worker {w['worker_id']} computed on {w['platform']!r}, "
                f"not {platform!r}")
    count = max(int(w["num_devices"]) for w in reports)
    if not ctx.rehearse and count != ctx.chips:
        raise RunFailure(f"workers saw {count} device(s), the cell asks "
                         f"for {ctx.chips}")
    return {"platform": platform, "kind": reports[0]["device_kind"],
            "count": count,
            "memory_peak_bytes": ctx.facts.get("memory_peak_bytes")}


def _child_pids() -> List[int]:
    """Live (non-zombie) children of this process, from /proc (copied from
    chip_smoke.py)."""
    me, out = os.getpid(), []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        if int(fields[1]) == me and fields[0] != "Z":
            out.append(int(pid))
    return out


def run_cell(args, started_at: float) -> Optional[Dict[str, Any]]:
    bench = _manifest.Benchmark(args.root or _manifest.REPO)
    if args.seconds is None:
        args.seconds = bench.doc["run_seconds"]
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell)
    if args.rehearse:
        from tpu_air.models.t5 import T5Config

        cfg = T5Config.tiny().to_dict()
        traffic = {**traffic, **traffic.get("rehearse", {})}
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        os.environ.setdefault("TPU_AIR_NUM_CHIPS", "8")
    else:
        found = chips_here()
        if found < cell["chips"]:
            raise RunFailure(
                f"cell {cell['name']} needs {cell['chips']} attached TPU "
                f"chip(s), found {found} "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    kind = bench.module("kinds", traffic["kind"])

    scratch = tempfile.mkdtemp(prefix="tpu_air-bench-")
    trace_dir = os.path.join(bench.root if args.root is None else scratch,
                             TRACE_DIR, cell["name"])
    ctx = Context(bench=bench, cell=cell, cfg=cfg, traffic=traffic,
                  seed=args.seed, seconds=float(args.seconds),
                  trace=bool(args.trace), rehearse=args.rehearse,
                  scratch=scratch, trace_dir=trace_dir)
    if ctx.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)

    try:
        return _drive(ctx, kind, args, started_at)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
        if ctx.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)


def _drive(ctx: Context, kind, args, started_at: float):
    import tpu_air
    from tpu_air import serve
    from tpu_air.core import chips
    from tpu_air.core.runtime import get_runtime

    bench, cell, cfg, traffic = ctx.bench, ctx.cell, ctx.cfg, ctx.traffic
    reports: List[Dict[str, Any]] = []
    marks = {"imports_s": time.time() - started_at}
    try:
        place_compile_cache()
        tpu_air.init()
        marks["init_s"] = time.time() - started_at - marks["imports_s"]
        kind.run(ctx)
        reports = get_runtime().device_reports()
    finally:
        t = time.time()
        serve.shutdown()
        tpu_air.shutdown()
        marks["shutdown_s"] = time.time() - t
    left = _child_pids()
    if left:
        raise RunFailure(f"processes left after shutdown: {left}")
    if chips.backend_live() and not ctx.rehearse:
        raise RunFailure("the driver started a JAX backend")
    if ctx.window_s is None:
        raise RunFailure("the kind measured no window")

    device = _device(ctx, reports, "cpu" if ctx.rehearse else "tpu")
    peak = None if ctx.rehearse else peaks.peak_for(device["kind"])
    trace = xplane.reduce_dir(ctx.trace_dir) if ctx.trace else None
    if ctx.trace and not ctx.rehearse:
        if trace is None or trace.busy_s <= 0:
            raise RunFailure("the traced run holds no device operation")
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s

    # setup_s: every second of this process that was not the measured
    # window — loading, compiling or loading programs, warming up, the
    # checks after the window and shutting down.  Work moved out of the
    # window in either direction shows here.
    ctx.facts["setup_s"] = time.time() - started_at - ctx.window_s
    rc = ReadContext(ctx.facts, trace, cfg, traffic, ctx.chips, peak)
    e2e = _read_metrics(bench, "end_to_end", ctx, rc)
    layer = _read_metrics(bench, "per_layer", ctx, rc) if ctx.trace else {}

    for w in reports:
        _say(worker=w.get("worker_id"), chips=w.get("chip_ids"),
             compile_s=w.get("compile_s"), cache_hits=w.get("cache_hits"),
             cold_compiles=w.get("cold_compiles"))
    if ctx.window_start is not None:
        # where set-up went: before the window, and after it (checks,
        # shutdown, reading the trace)
        marks["before_window_s"] = ctx.window_start - started_at
        marks["after_window_s"] = (ctx.facts["setup_s"]
                                   - marks["before_window_s"])
    _say(cell=cell["name"], seed=args.seed, window_s=ctx.window_s,
         setup=marks,
         notes=ctx.notes, problems=ctx.problems,
         idle_share=None if trace is None else trace.idle_share,
         end_to_end=e2e if ctx.trace else None)
    if ctx.rehearse:
        print(f"rehearsal of {cell['name']}: "
              f"{'ok' if not ctx.problems else ctx.problems}; metrics read: "
              f"{sorted(e2e) + sorted(layer)} (a CPU run: no result line)")
        return None if not ctx.problems else {"problems": ctx.problems}

    metrics = layer if ctx.trace else e2e
    result: Dict[str, Any] = {
        "correct": not ctx.problems,
        "attempted": ctx.attempted,
        "failed": ctx.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace is not None:
        result["breakdown"] = {"device_ops": trace.device_ops(),
                               "idle_gaps": trace.idle_gaps()}
    return result


def main(argv: List[str], started_at: Optional[float] = None) -> int:
    started_at = started_at or time.time()
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, T5Config.tiny(): control flow only, prints "
                         "no result line")
    ap.add_argument("--root", default=None,
                    help="directory holding BENCHMARK.json (tests)")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args, started_at)
    except (RunFailure, _manifest.ManifestError, peaks.UnknownDevice) as e:
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    if args.rehearse:
        return 0 if result is None else 1
    print(json.dumps(result), flush=True)
    return 0
