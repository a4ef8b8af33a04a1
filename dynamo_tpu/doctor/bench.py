"""`python -m dynamo_tpu.doctor bench` — the perf-ledger view.

Two modes (docs/observability.md "Perf ledger & regression gate"):

- trajectory: ``doctor bench BENCH_a.json BENCH_b.json ...``
  renders every recorded round through `bench.ledger.normalize_run`
  — ok rounds with their metrics, partial rounds with their phase
  errors, outage rounds as honest holes carrying the preflight
  diagnosis (timeout vs OOM) — plus consecutive-round
  deltas with per-metric noise bounds.

- gate: ``doctor bench --gate baseline.json current.json`` compares
  two deterministic perf records (`dynamo_tpu.bench.perf`) against
  `ledger.GATE_THRESHOLDS` and exits nonzero on any regression past
  threshold; `make perf-gate` wires this into CI with the checked-in
  `benchmarks/perf_baseline.json`.
"""

from __future__ import annotations

import argparse
import json

from dynamo_tpu.bench.ledger import (
    LEDGER_METRICS,
    gate_compare,
    is_perf_record,
    load_run,
    trajectory_deltas,
)

_STATUS_TAG = {"ok": "ok     ", "partial": "PARTIAL", "outage": "OUTAGE "}


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.3f}".rstrip("0").rstrip(".")
    return str(v)


def render_trajectory(records: list) -> str:
    """The full history as text: one block per round, then the delta
    table. Outage rounds render their diagnosis, never a fake zero."""
    lines = ["perf ledger trajectory"]
    for rec in records:
        rnd = f"r{rec.round:02d}" if rec.round is not None else rec.label
        head = f"  {rnd}  [{_STATUS_TAG.get(rec.status, rec.status)}]"
        if getattr(rec, "kind", "bench") == "multichip":
            nd = int(rec.metrics.get("n_devices") or 0)
            if rec.status == "ok":
                tail = str(rec.raw.get("tail") or "").strip()
                lines.append(f"{head}  multichip dryrun passed on "
                             f"{nd} device(s)")
                if tail:
                    lines.append(f"        {tail.splitlines()[0][:110]}")
            else:
                diag = rec.diagnosis or {}
                lines.append(f"{head}  multichip dryrun "
                             f"rc={rec.raw.get('rc')} on {nd} device(s)"
                             f" — no serving evidence this round")
                lines.append(
                    f"        cause: {diag.get('kind', 'unknown')} — "
                    f"{(diag.get('detail') or '(no detail)')[:110]}")
            continue
        if rec.status == "outage":
            diag = rec.diagnosis or {}
            lines.append(f"{head}  no number this round")
            lines.append(f"        cause: {diag.get('kind', 'unknown')}"
                         f" — {diag.get('detail', '(no detail)')}")
            if rec.oom_report:
                # memory-ledger forensics (engine/memory.py): the r03
                # fix — attribution instead of a bare
                # RESOURCE_EXHAUSTED tail
                from dynamo_tpu.engine.memory import \
                    format_oom_attribution
                lines.append("        oom attribution: "
                             + format_oom_attribution(rec.oom_report)
                             + "  (`doctor memory <crash file>` for "
                             "the full ledger)")
            continue
        lines.append(f"{head}  {_fmt(rec.value)} tok/s/chip")
        shown = []
        for spec in LEDGER_METRICS:
            if spec.key == "tok_s_chip":
                continue
            v = rec.metrics.get(spec.key)
            if v is not None:
                shown.append(f"{spec.label} {_fmt(v)}{spec.unit}")
        if shown:
            lines.append("        " + "  ·  ".join(shown))
        if rec.status == "partial":
            diag = rec.diagnosis or {}
            lines.append(f"        partial: {len(rec.errors)} phase "
                         f"error(s), first classed "
                         f"{diag.get('kind', 'unknown')}")
            for e in rec.errors[:3]:
                lines.append(f"          - {e[:110]}")

    deltas = trajectory_deltas(records)
    if deltas:
        lines.append("")
        lines.append("  deltas (consecutive rounds carrying the metric; "
                     "~ = inside noise bound)")
        lines.append(f"  {'metric':<22}{'from':>6}{'to':>6}"
                     f"{'base':>12}{'cur':>12}{'delta%':>9}"
                     f"{'noise%':>8}  verdict")
        mark = {"noise": "~", "better": "+", "worse": "!"}
        for row in deltas:
            lines.append(
                f"  {row['label']:<22}{row['from']:>6}{row['to']:>6}"
                f"{_fmt(row['base']):>12}{_fmt(row['cur']):>12}"
                f"{_fmt(row['delta_pct']):>9}{_fmt(row['noise_pct']):>8}"
                f"  {mark.get(row['verdict'], '?')} {row['verdict']}")
    return "\n".join(lines)


def render_gate(rows: list, failed: bool) -> str:
    lines = ["perf gate (deterministic chip-free metrics vs baseline)"]
    lines.append(f"  {'metric':<26}{'baseline':>12}{'current':>12}"
                 f"{'delta':>10}{'allowed':>10}  result")
    for r in rows:
        res = "ok" if r["ok"] else "REGRESSION"
        note = f"  ({r['note']})" if r.get("note") else ""
        lines.append(
            f"  {r['metric']:<26}{_fmt(r['base']):>12}"
            f"{_fmt(r['cur']):>12}{_fmt(r['delta']):>10}"
            f"{_fmt(r['allowed']):>10}  {res}{note}")
    lines.append("")
    lines.append("  GATE " + ("FAILED — at least one metric regressed "
                              "past its threshold" if failed
                              else "PASSED"))
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m dynamo_tpu.doctor bench",
        description="bench-trajectory ledger and deterministic perf gate")
    p.add_argument("runs", nargs="+",
                   help="BENCH_*.json / MULTICHIP_*.json files "
                        "(trajectory) or, with --gate, exactly: "
                        "baseline.json current.json")
    p.add_argument("--gate", action="store_true",
                   help="compare two perf records against the "
                        "regression thresholds; exit 1 on regression")
    p.add_argument("--json", action="store_true",
                   help="emit the normalized records / gate rows as "
                        "JSON instead of text")
    args = p.parse_args(argv)

    if args.gate:
        if len(args.runs) != 2:
            print("--gate needs exactly two files: baseline current")
            return 2
        with open(args.runs[0], "r", encoding="utf-8") as f:
            base = json.load(f)
        with open(args.runs[1], "r", encoding="utf-8") as f:
            cur = json.load(f)
        for name, rec, path in (("baseline", base, args.runs[0]),
                                ("current", cur, args.runs[1])):
            if not is_perf_record(rec):
                print(f"{name} file is not a perf record "
                      f"(schema != dynamo-perf-v1): {path}")
                return 2
        rows, failed = gate_compare(base, cur)
        if args.json:
            print(json.dumps({"rows": rows, "failed": failed},
                             indent=1, sort_keys=True))
        else:
            print(render_gate(rows, failed))
        return 1 if failed else 0

    try:
        records = [load_run(path) for path in args.runs]
    except (OSError, json.JSONDecodeError) as e:
        print(f"cannot load run: {e}")
        return 1
    records.sort(key=lambda r: (r.round is None,
                                r.round if r.round is not None else 0,
                                r.label))
    if args.json:
        print(json.dumps([{
            "label": r.label, "round": r.round, "status": r.status,
            "kind": r.kind, "value": r.value, "metrics": r.metrics,
            "errors": r.errors, "diagnosis": r.diagnosis,
        } for r in records], indent=1, sort_keys=True))
    else:
        print(render_trajectory(records))
    return 0
