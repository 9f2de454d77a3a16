#!/usr/bin/env python3
"""Where a device program's time goes, by part of the model: one row a scope
path (``encoder/*/self_attn/attn_softmax``: flax module names and the
``jax.named_scope`` words of docs/OBSERVABILITY.md, layers merged), with the
device milliseconds an execution of the program, the share of the program's
operation time, the number of operations an execution, and the compiler's own
``bytes_accessed`` and ``flops`` of the row over its time (GB/s, TFLOP/s).
Read from a capture's event metadata by ``benchmark/scopes.py``; a fusion
counts whole for the scope of its root.

    python benchmark/tools/scope_table.py [capture.xplane.pb] [--module lm_paged_decode_step] [--depth 2] [--ops ^gmm] [--unscoped 10]
    python benchmark/tools/scope_table.py --workload olmoe-serve-decode [--seed 7]

The first form reads a capture (default: the newest under ``.bench_traces/``)
and prints a table for ``jit_<module>``, or for every program with at least
1 % of the operation time.  The harness removes a run's capture when the run
ends, so the second form makes the cell's ``--trace 1`` run itself (on the
chip), prints the tables of its capture and then its result line.  ``--out``
sends the tables to a file (a chip call shows only the end of its output).
"""

import argparse
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def programs(plane):
    """``(name, program id, executions, operation seconds)``, longest first;
    executions are the program's time on ``XLA Modules`` over its median
    execution (a capture cuts its first and last short)."""
    from benchmark import stats, xplane

    rows = []
    for pid, (name, lasted) in plane.programs().items():
        busy = xplane.total(xplane.union(
            [(s, e) for _, s, e in plane.of_program(pid)]))
        rows.append((name, pid, sum(lasted) / stats.percentile(lasted, 0.5),
                     busy))
    return sorted(rows, key=lambda r: -r[3])


def table(plane, pid, runs, depth=None):
    """Rows ``(path, ms an execution, share %, operations an execution,
    GB/s, TFLOP/s)`` of one program, longest first, and its ms an execution."""
    from benchmark import scopes, xplane

    ops = plane.of_program(pid)
    rows = {}
    for ident, start, end in ops:
        md = plane.metadata[ident]
        row = rows.setdefault(
            scopes.part_path(plane.parts(ident), depth), [[], 0, 0, 0])
        row[0].append((start, end))
        row[1] += 1
        row[2] += md.get("bytes_accessed", 0)
        row[3] += md.get("flops", 0)
    whole = xplane.total(xplane.union([(s, e) for _, s, e in ops]))
    out = []
    for path, (spans_, count, nbytes, flops) in rows.items():
        t = xplane.total(xplane.union(spans_))
        out.append((path, 1e3 * t / runs, 100.0 * t / whole, count / runs,
                    nbytes / t / 1e9 if t else 0.0,
                    flops / t / 1e12 if t else 0.0))
    return sorted(out, key=lambda r: -r[1]), 1e3 * whole / runs


def named(plane, pid, pattern):
    """Seconds of the program's operations whose NAME matches ``pattern``
    (``^gmm``: what ``moe_expert_roofline`` reads), to hold a scope against."""
    from benchmark import xplane

    pat = re.compile(pattern)
    return xplane.total(xplane.union([
        (s, e) for i, s, e in plane.of_program(pid)
        if pat.search(xplane.op_name(plane.metadata[i]["name"]))]))


def unscoped(plane, pid, runs, top):
    """The program's operations no part covers, grouped by name without its
    number: ``(name, hlo category, ms an execution, operations an execution,
    a path if any)``, longest first."""
    from benchmark import scopes, xplane

    rows = {}
    for ident, start, end in plane.of_program(pid):
        if scopes.covered(plane.parts(ident)):
            continue
        md = plane.metadata[ident]
        stem = re.sub(r"[.\d]+$", "", xplane.op_name(md["name"]))
        row = rows.setdefault((stem, md.get("hlo_category", "")),
                              [0.0, 0, md.get("tf_op", "")])
        row[0] += end - start
        row[1] += 1
    found = sorted(rows.items(), key=lambda kv: -kv[1][0])[:top]
    return [(stem, cat, 1e3 * t / runs, n / runs, tf_op)
            for (stem, cat), (t, n, tf_op) in found]


def report(path, module=None, depth=None, out=sys.stdout, ops=None,
           top_unscoped=0):
    from benchmark import scopes, stats
    from benchmark.readers.module_hbm_share import whole_executions

    planes = scopes.read(path)
    print(f"{path}: {os.path.getsize(path) / 1e6:.1f} MB, read in "
          f"{scopes.SECONDS[path]:.2f} s", file=out)
    if not planes:
        print(f"scope_table: no device plane in {path}", file=sys.stderr)
        return 1
    plane = planes[min(planes)]
    found = programs(plane)
    total = sum(r[3] for r in found) or 1.0
    if module is not None:
        pid = plane.program_id(module)
        found = [r for r in found if r[1] == pid]
        if not found:
            print(f"scope_table: no program jit_{module} in {path}",
                  file=sys.stderr)
            return 1
    for name, pid, runs, busy in found:
        if module is None and busy < 0.01 * total:
            continue
        rows, ms = table(plane, pid, runs, depth)
        whole = whole_executions(plane, name[len("jit_"):])
        print(f"\n{name}: {runs:.3g} executions, {ms:.3f} ms of operations an "
              f"execution, {100.0 * busy / total:.1f} % of the capture's "
              f"operation time; {len(whole)} whole executions of mean "
              f"{1e3 * sum(whole) / len(whole):.3f} ms, median "
              f"{1e3 * stats.percentile(whole, 0.5):.3f} ms (what a "
              "whole-step roofline divides its floor by: the mean)",
              file=out)
        print(f"{'ms':>9} {'share %':>8} {'ops':>7} {'GB/s':>8} "
              f"{'TFLOP/s':>8}  scope", file=out)
        for path_, t, share, count, gbs, tfs in rows:
            print(f"{t:9.3f} {share:8.2f} {count:7.1f} {gbs:8.1f} "
                  f"{tfs:8.2f}  {path_}", file=out)
        for stem, cat, t, n, tf_op in unscoped(plane, pid, runs, top_unscoped):
            print(f"{t:9.3f} {'':8} {n:7.1f}  (unscoped) {stem} [{cat}] "
                  f"{tf_op}", file=out)
        if ops:
            t = named(plane, pid, ops)
            print(f"{1e3 * t / runs:9.3f} {100.0 * t / busy:8.2f}  operations "
                  f"named {ops}", file=out)
    return 0


def _out(args):
    if not args.out:
        return sys.stdout
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    return open(args.out, "a")


def run_cell(args) -> int:
    """The cell's own traced run, with the tables of its capture printed
    before the harness removes it."""
    from benchmark import harness, spans

    drive = harness._drive

    def drive_then_report(ctx, *rest):
        result = drive(ctx, *rest)
        capture = spans.newest_xplane()
        if capture is None:
            print("scope_table: the run left no capture", file=sys.stderr)
        else:
            report(capture, args.module, args.depth, _out(args), args.ops,
                   args.unscoped)
        return result

    harness._drive = drive_then_report
    return harness.main(["--workload", args.workload, "--seed",
                         str(args.seed), "--trace", "1"]
                        + (["--seconds", str(args.seconds)]
                           if args.seconds else []))


def main() -> int:
    sys.path.insert(0, ROOT)
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get(
        "PYTHONPATH", "")
    ap = argparse.ArgumentParser()
    ap.add_argument("capture", nargs="?", default=None)
    ap.add_argument("--module", default=None,
                    help="the jitted function's name: jit_<module>")
    ap.add_argument("--depth", type=int, default=None,
                    help="keep this many leading components of a path")
    ap.add_argument("--ops", default=None,
                    help="also the time of the operations so NAMED (^gmm)")
    ap.add_argument("--unscoped", type=int, default=0,
                    help="also list this many kinds of unscoped operations")
    ap.add_argument("--workload", default=None,
                    help="run this cell traced and report on its capture")
    ap.add_argument("--out", default=None,
                    help="append the tables to this file")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args()
    if args.workload:
        return run_cell(args)
    from benchmark import spans

    capture = args.capture or spans.newest_xplane()
    if capture is None:
        print("scope_table: no capture given and none under .bench_traces/",
              file=sys.stderr)
        return 2
    return report(capture, args.module, args.depth, _out(args), args.ops,
                   args.unscoped)


if __name__ == "__main__":
    sys.exit(main())
