"""Observability snapshot tool (`make obs-dump`, CI artifact checks).

Four subcommands — three over the canonical JSON snapshot format
(consensus_specs_tpu/obs/export.py), one over the span-dump format
(consensus_specs_tpu/obs/timeline.py):

  check FILE   validate an on-disk snapshot: parseable, right version,
               canonical bytes, and Prometheus round-trip (the text
               exposition's value set must equal the JSON's). Exit 0 ok,
               1 invalid, 2 unreadable. CI runs this over every uploaded
               artifact.
  prom FILE    render the snapshot as Prometheus text exposition (stdout),
               for scraping/diffing with standard tooling.
  table FILE   human-oriented summary: counters and gauges sorted by
               series key, histograms as count/sum/p50/p99. `--top N`
               flips to hot-spot mode: the N highest-value counters and
               gauges and the N fattest-p99 histograms, flat, hottest
               first.
  trace FILE   render a span dump (timeline.write_span_dump) as Chrome
               trace event JSON — load the output in Perfetto /
               chrome://tracing to see spans in per-thread lanes with
               flow arrows following each request across them. `-o OUT`
               writes to a file instead of stdout.

`FILE` may be `-` for stdin, so `... | obs_dump.py check -` works in a
pipeline.
"""
from __future__ import annotations

import argparse
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from consensus_specs_tpu.obs import export as obs_export  # noqa: E402


def _read(path: str) -> str:
    if path == "-":
        return sys.stdin.read()
    with open(path) as f:
        return f.read()


def cmd_check(path: str) -> int:
    try:
        text = _read(path)
    except OSError as exc:
        print(f"obs-dump: cannot read {path}: {exc}", file=sys.stderr)
        return 2
    ok, reason = obs_export.validate_snapshot_text(text)
    if not ok:
        print(f"obs-dump: INVALID snapshot {path}: {reason}", file=sys.stderr)
        return 1
    import json

    snap = json.loads(text)
    json_vals = obs_export.snapshot_value_set(snap)
    prom_vals = obs_export.prometheus_value_set(obs_export.prometheus_text(snap))
    if json_vals != prom_vals:
        only_j = sorted(set(json_vals) - set(prom_vals))[:5]
        only_p = sorted(set(prom_vals) - set(json_vals))[:5]
        diff = sorted(k for k in set(json_vals) & set(prom_vals)
                      if json_vals[k] != prom_vals[k])[:5]
        print(f"obs-dump: EXPORTER DISAGREEMENT {path}: "
              f"json-only={only_j} prom-only={only_p} differing={diff}",
              file=sys.stderr)
        return 1
    n = (len(snap.get("counters", {})) + len(snap.get("gauges", {}))
         + len(snap.get("histograms", {})))
    print(f"obs-dump: OK {path} ({n} series, version {snap['version']})")
    return 0


def _load(path: str) -> dict:
    text = _read(path)
    ok, reason = obs_export.validate_snapshot_text(text)
    if not ok:
        raise SystemExit(f"obs-dump: INVALID snapshot {path}: {reason}")
    import json

    return json.loads(text)


def cmd_prom(path: str) -> int:
    sys.stdout.write(obs_export.prometheus_text(_load(path)))
    return 0


def _subsystem(series_key: str) -> str:
    """Grouping prefix of a series key: the first `_`-delimited token of
    the metric name (`sched_queue_depth{...}` -> `sched`). Series whose
    name has no underscore group under the whole name."""
    name = series_key.split("{", 1)[0]
    return name.split("_", 1)[0]


def cmd_table(path: str, top: int | None = None) -> int:
    """Human-oriented summary, grouped by subsystem prefix so the lanes a
    snapshot covers (sched_*, bls_*, gossip_*, fault_*, ...) read as
    blocks instead of one interleaved flat list. Within a group, rows
    keep canonical order: counters, then gauges, then histograms, each
    sorted by series key. With --top N the grouping drops: the N hottest
    counters/gauges (by value) and histograms (by p99) print flat,
    hottest first — what an operator scans during an incident."""
    snap = _load(path)
    if top is not None:
        return _table_top(snap, top)
    rows = []
    for key, v in sorted(snap.get("counters", {}).items()):
        rows.append((_subsystem(key), key, "counter", f"{v:g}"))
    for key, v in sorted(snap.get("gauges", {}).items()):
        rows.append((_subsystem(key), key, "gauge", f"{v:g}"))
    for key, h in sorted(snap.get("histograms", {}).items()):
        rows.append((_subsystem(key), key, "histogram",
                     f"count={h['count']} sum={h['sum']:.6g} "
                     f"p50={h['p50']:.6g} p99={h['p99']:.6g}"))
    if not rows:
        print("(empty snapshot)")
        return 0
    width = max(len(r[1]) for r in rows)
    by_group: dict = {}
    for group, key, kind, val in rows:
        by_group.setdefault(group, []).append((key, kind, val))
    for i, group in enumerate(sorted(by_group)):
        if i:
            print()
        print(f"[{group}]")
        for key, kind, val in by_group[group]:
            print(f"  {key:<{width}}  {kind:<9}  {val}")
    if "meta" in snap:
        print(f"\nmeta: {snap['meta']}")
    return 0


def _table_top(snap: dict, top: int) -> int:
    """Hot-spot view: counter/gauge rows ranked by value, histogram rows
    by p99 — series key ties break alphabetically so equal snapshots
    print identically."""
    scalars = ([(v, key, "counter") for key, v in
                snap.get("counters", {}).items()]
               + [(v, key, "gauge") for key, v in
                  snap.get("gauges", {}).items()])
    scalars.sort(key=lambda r: (-r[0], r[1]))
    hists = sorted(((h["p99"], key, h) for key, h in
                    snap.get("histograms", {}).items()),
                   key=lambda r: (-r[0], r[1]))
    if not scalars and not hists:
        print("(empty snapshot)")
        return 0
    rows = []
    for v, key, kind in scalars[:top]:
        rows.append((key, kind, f"{v:g}"))
    for p99, key, h in hists[:top]:
        rows.append((key, "histogram",
                     f"p99={p99:.6g} p50={h['p50']:.6g} "
                     f"count={h['count']} sum={h['sum']:.6g}"))
    width = max(len(r[0]) for r in rows)
    if scalars:
        print(f"[top {min(top, len(scalars))} counters/gauges by value]")
        for key, kind, val in rows[:len(scalars[:top])]:
            print(f"  {key:<{width}}  {kind:<9}  {val}")
    if hists:
        if scalars:
            print()
        print(f"[top {min(top, len(hists))} histograms by p99]")
        for key, kind, val in rows[len(scalars[:top]):]:
            print(f"  {key:<{width}}  {kind:<9}  {val}")
    return 0


def cmd_trace(path: str, output: str) -> int:
    """Span dump -> Chrome trace event JSON (Perfetto-loadable)."""
    from consensus_specs_tpu.obs import timeline as obs_timeline

    try:
        text = _read(path)
    except OSError as exc:
        print(f"obs-dump: cannot read {path}: {exc}", file=sys.stderr)
        return 2
    try:
        spans = obs_timeline.load_span_dump(text)
    except ValueError as exc:
        print(f"obs-dump: INVALID span dump {path}: {exc}", file=sys.stderr)
        return 1
    out = obs_export.canonical_json(obs_timeline.chrome_trace(spans))
    if output == "-":
        sys.stdout.write(out)
    else:
        with open(output, "w") as f:
            f.write(out)
        n = sum(1 for s in spans if s.get("t_start") is not None)
        print(f"obs-dump: wrote {output} ({n} spans)")
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = parser.add_subparsers(dest="cmd", required=True)
    for name, doc in (("check", "validate canonicality + exporter agreement"),
                      ("prom", "render Prometheus text exposition"),
                      ("table", "human-oriented summary"),
                      ("trace", "span dump -> Chrome/Perfetto trace JSON")):
        p = sub.add_parser(name, help=doc)
        p.add_argument("file", help="snapshot path, or - for stdin")
        if name == "table":
            p.add_argument("--top", type=int, default=None, metavar="N",
                           help="flat hot-spot view: top N counters/gauges "
                                "by value, histograms by p99")
        if name == "trace":
            p.add_argument("-o", "--output", default="-",
                           help="output path (default: stdout)")
    args = parser.parse_args(argv)
    if args.cmd == "check":
        return cmd_check(args.file)
    if args.cmd == "prom":
        return cmd_prom(args.file)
    if args.cmd == "table":
        return cmd_table(args.file, top=args.top)
    return cmd_trace(args.file, args.output)


if __name__ == "__main__":
    try:
        rc = main()
        sys.stdout.flush()
    except BrokenPipeError:
        # downstream pager/head closed the pipe — not an error
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        rc = 0
    raise SystemExit(rc)
