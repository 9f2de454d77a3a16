#!/usr/bin/env python
"""trace_dump — pull airtrace spans off a running dashboard (or the local
recorder) and write chrome://tracing-loadable JSON.

Usage::

    # list recent traces on a live dashboard
    python tools/trace_dump.py --url http://127.0.0.1:8265 --list

    # export everything (or one trace) to a file for chrome://tracing /
    # ui.perfetto.dev
    python tools/trace_dump.py --url http://127.0.0.1:8265 -o trace.json
    python tools/trace_dump.py --url http://127.0.0.1:8265 \
        --trace-id 0af7651916cd43dd8448eb211c80319c -o one_request.json

    # no dashboard: dump THIS process's recorder (mostly for scripts that
    # import tpu_air, enable tracing, run work, then exec this file)
    python tools/trace_dump.py --local -o trace.json

    # render a flight-recorder postmortem (written on worker death when
    # TPU_AIR_POSTMORTEM_DIR is set) as a human-readable report
    python tools/trace_dump.py --postmortem /var/crash/postmortem-...json

See docs/OBSERVABILITY.md for the export workflow.
"""

import argparse
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fetch(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def render_postmortem(data: dict, out=sys.stdout) -> None:
    """Human-readable report from one postmortem JSON (schema
    tpu-air-postmortem/1 — observability/postmortem.py)."""
    w = out.write
    ctx = data.get("context") or {}
    w(f"postmortem: {data.get('reason')}\n")
    w(f"  captured at unix_time={data.get('unix_time')}\n")
    if ctx:
        w(f"  worker={ctx.get('worker_id')} pid={ctx.get('pid')} "
          f"actor={ctx.get('actor_id')} busy_task={ctx.get('busy_task')}\n")
        if ctx.get("outstanding_tasks"):
            w(f"  outstanding tasks ({len(ctx['outstanding_tasks'])}):\n")
            for t in ctx["outstanding_tasks"]:
                w(f"    - {t}\n")
    cluster = data.get("cluster") or {}
    if cluster.get("initialized"):
        res = cluster.get("resources", {})
        w(f"\ncluster: {len(cluster.get('workers', {}))} workers, "
          f"{len(cluster.get('actors', {}))} actors, "
          f"queue_depth={cluster.get('queue_depth')}, "
          f"cpus={res.get('cpu')} chips={res.get('chip')}\n")
        for aid, a in (cluster.get("actors") or {}).items():
            flag = " DEAD" if a.get("dead") else ""
            w(f"  actor {aid} ({a.get('name') or 'anon'}) "
              f"worker={a.get('worker_id')} pending={a.get('pending')}{flag}\n")
    engines = data.get("engines") or {}
    for name, s in engines.items():
        if not isinstance(s, dict):
            continue
        perf = s.get("perf") or {}
        totals = perf.get("totals") or {}
        goodput = perf.get("goodput") or {}
        w(f"\nengine {name}: tokens={s.get('tokens_generated')} "
          f"retired={s.get('requests_retired')} "
          f"queue={s.get('queue_depth')}\n")
        if totals:
            share = totals.get("roofline_fraction")  # None off-chip
            w(f"  roofline_fraction="
              f"{'n/a' if share is None else format(share, '.3f')} "
              f"flops/s={totals.get('flops_per_s', 0):.3e}\n")
        if goodput:
            w(f"  goodput_ratio={goodput.get('goodput_ratio', 0):.3f} "
              f"(useful={goodput.get('useful', 0)} "
              f"wasted={goodput.get('wasted', 0)})\n")
    slo = data.get("slo")
    if isinstance(slo, dict) and slo.get("slos"):
        burning = set(slo.get("burning") or [])
        w("\nslo state:\n")
        for s in slo["slos"]:
            mark = " BURNING" if s["name"] in burning else ""
            rates = " ".join(
                f"{int(win['window_s'])}s={win['burn_rate']:.2f}x"
                for win in s.get("windows", []))
            w(f"  {s['name']} (obj={s['objective']}): {rates}{mark}\n")
    traces = data.get("traces") or {}
    spans = traces.get("spans") or {}
    for tid, span_list in spans.items():
        w(f"\ntrace {tid} ({len(span_list)} spans):\n")
        by_id = {s["span_id"]: s for s in span_list}
        roots = [s for s in span_list
                 if not s.get("parent_id") or s["parent_id"] not in by_id]
        kids: dict = {}
        for s in span_list:
            kids.setdefault(s.get("parent_id"), []).append(s)

        def _walk(span, depth):
            dur_ms = (span["end_ns"] - span["start_ns"]) / 1e6
            err = (f"  [{span['status']}]"
                   if str(span.get("status", "ok")).startswith("error") else "")
            w(f"  {'  ' * depth}{span['name']}  {dur_ms:.2f} ms{err}\n")
            for c in sorted(kids.get(span["span_id"], []),
                            key=lambda x: x["start_ns"]):
                _walk(c, depth + 1)

        for r in sorted(roots, key=lambda x: x["start_ns"]):
            _walk(r, 1)
    recent = traces.get("recent") or []
    if recent:
        w(f"\nrecent traces ({len(recent)}):\n")
        for t in recent:
            w(f"  {t['trace_id']}  {t['root']:<30} "
              f"{t['spans']:>4} spans  {t['duration_ms']:.2f} ms\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", default="http://127.0.0.1:8265",
                    help="dashboard base URL (default %(default)s)")
    ap.add_argument("--trace-id", default=None,
                    help="export only this trace (32-hex id)")
    ap.add_argument("--list", action="store_true",
                    help="print recent trace summaries instead of exporting")
    ap.add_argument("--local", action="store_true",
                    help="dump this process's recorder, no dashboard needed")
    ap.add_argument("-o", "--output", default="trace.json",
                    help="output file for the chrome-trace JSON")
    ap.add_argument("--postmortem", default=None, metavar="FILE",
                    help="render a flight-recorder postmortem JSON instead")
    args = ap.parse_args(argv)

    if args.postmortem:
        from tpu_air.observability import postmortem

        render_postmortem(postmortem.load(args.postmortem))
        return 0

    if args.local:
        from tpu_air.observability import trace_export, tracing

        if args.list:
            for t in tracing.trace_summaries():
                print(f"{t['trace_id']}  {t['root']:<30} "
                      f"{t['spans']:>4} spans  {t['duration_ms']:.2f} ms")
            return 0
        n = trace_export.export_file(args.output, trace_id=args.trace_id)
        print(f"wrote {n} spans to {args.output}")
        return 0

    base = args.url.rstrip("/")
    if args.list:
        payload = _fetch(f"{base}/api/traces")
        if not payload.get("enabled"):
            print("tracing is disabled on the target "
                  "(set TPU_AIR_TRACE=1 or call tracing.enable())",
                  file=sys.stderr)
        for t in payload.get("traces", []):
            print(f"{t['trace_id']}  {t['root']:<30} "
                  f"{t['spans']:>4} spans  {t['duration_ms']:.2f} ms"
                  + (f"  [{t['errors']} errors]" if t.get("errors") else ""))
        return 0

    url = f"{base}/api/traces/export"
    if args.trace_id:
        url += f"?trace_id={args.trace_id}"
    doc = _fetch(url)
    with open(args.output, "w") as f:
        json.dump(doc, f)
    n = doc.get("otherData", {}).get("spans", 0)
    print(f"wrote {n} spans to {args.output} "
          "(load in chrome://tracing or ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
