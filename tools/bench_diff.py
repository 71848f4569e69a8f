#!/usr/bin/env python3
"""Perf-trajectory sentinel: gate every bench round against its history.

Until this tool nothing had ever compared two ``BENCH_r*.json`` /
``MULTICHIP_r*.json`` rounds — regressions (and whole-round failures
like an rc=124 ``parsed: null`` round) were only caught by a human
reading JSON. ``bench_diff`` parses every round,
normalizes metric lines across the schema drift between rounds
(``parsed`` dicts, suite lines, per-query roofline lines, trailing
driver-metric JSON in the tail), and exits nonzero when any tracked
higher-is-better metric drops more than ``--threshold`` (default 15%,
noise headroom) below the best prior round *for the same metric name* —
renamed workloads (e.g. the r01→r02 sf0.2→sf2.0 switch) start a fresh
history instead of comparing apples to oranges.

Round tolerance, by design:
- ``rc != 0`` or ``parsed: null``  -> the round is reported as degraded
  and contributes no baselines, but never fails the gate by itself
  (a broken round is the bench runner's bug, not a perf regression);
- missing ``parsed`` key (MULTICHIP schema) -> metrics come from tail
  JSON lines only; a tail without metric lines is fine.

On/off tracker rounds (``BENCH_AUTOTUNE_r*.json``,
``BENCH_SORTWIN_r*.json``) are gated too: each query contributes
``query:<q>:speedup`` (wall_off/wall_on — losing a previously-held
speedup trips the gate) and ``query:<q>:roofline_util``; a round with
any ``identical: false`` query is degraded (a wrong answer has no
legitimate speed).

CLI:
    python tools/bench_diff.py [--dir .] [--threshold 0.15] [--json]

Exit codes: 0 clean, 1 regression(s), 2 usage/IO error. Wired into
tests/run_slow_lane.sh so every future round is gated on its history.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

# only metrics where bigger is better participate in the gate; latencies
# and counts drift for legitimate reasons (deeper coverage, more queries)
_HIGHER_BETTER = re.compile(
    r"(rows_per_sec|queries_per_sec|roofline_util|utilization"
    r"|queries_per_s|speedup)$")

_ROUND_RE = re.compile(r"_r(\d+)\.json$")

#: artifact families and their globs; the two on/off tracker families
#: (autotune, sortwin) share one schema and one extractor
_KINDS = (("bench", "BENCH_r*.json"),
          ("multichip", "MULTICHIP_r*.json"),
          ("autotune", "BENCH_AUTOTUNE_r*.json"),
          ("sortwin", "BENCH_SORTWIN_r*.json"),
          ("serveopen", "BENCH_SERVEOPEN_r*.json"))
_ONOFF_KINDS = frozenset({"autotune", "sortwin"})


def _json_lines(tail: str) -> List[Dict]:
    out = []
    for line in (tail or "").splitlines():
        line = line.strip()
        if not (line.startswith("{") and line.endswith("}")):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict):
            out.append(obj)
    return out


def _num(v) -> Optional[float]:
    return float(v) if isinstance(v, (int, float)) and not isinstance(
        v, bool) else None


def extract_metrics(doc: Dict) -> Dict[str, float]:
    """Normalize one round's artifact into {metric_name: value}.

    Sources, newest schema first (later assignments win so the parsed
    summary — the round's authoritative number — overrides a stale
    tail duplicate):
    - tail JSON lines: ``{"suite": s, "rows_per_sec": v}``,
      ``{"query": q, "roofline_util": u}``, ``{"metric": m, "value": v}``
      (plus its ``utilization`` rider);
    - the ``parsed`` dict (BENCH schema): ``metric``/``value`` plus
      ``utilization``.
    """
    metrics: Dict[str, float] = {}
    for obj in _json_lines(doc.get("tail", "")):
        if "suite" in obj:
            v = _num(obj.get("rows_per_sec"))
            if v is not None:
                metrics[f"suite:{obj['suite']}:rows_per_sec"] = v
        if "query" in obj:
            u = _num(obj.get("roofline_util"))
            if u is not None:
                metrics[f"query:{obj['query']}:roofline_util"] = u
        if "metric" in obj:
            v = _num(obj.get("value"))
            if v is not None:
                metrics[str(obj["metric"])] = v
            u = _num(obj.get("utilization"))
            if u is not None:
                metrics[f"{obj['metric']}:utilization"] = u
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and "metric" in parsed:
        v = _num(parsed.get("value"))
        if v is not None:
            metrics[str(parsed["metric"])] = v
        u = _num(parsed.get("utilization"))
        if u is not None:
            metrics[f"{parsed['metric']}:utilization"] = u
    return metrics


def extract_onoff_metrics(doc: Dict) -> Dict[str, float]:
    """Normalize an on/off tracker artifact (BENCH_AUTOTUNE_r*,
    BENCH_SORTWIN_r*) into {metric_name: value}.

    Per query: ``speedup`` = wall_off_ms / wall_on_ms (>1 means the
    feature won; higher is better, so a later round losing a win it
    used to have trips the gate) and ``roofline_util`` when the round
    recorded it. A query with ``identical: false`` contributes nothing
    — a wrong answer has no legitimate speed.
    """
    metrics: Dict[str, float] = {}
    for q, row in sorted((doc.get("queries") or {}).items()):
        if not isinstance(row, dict) or row.get("identical") is False:
            continue
        off, on = _num(row.get("wall_off_ms")), _num(row.get("wall_on_ms"))
        if off is not None and on is not None and on > 0:
            metrics[f"query:{q}:speedup"] = round(off / on, 4)
        u = _num(row.get("roofline_util"))
        if u is not None:
            metrics[f"query:{q}:roofline_util"] = u
    return metrics


def load_rounds(bench_dir: str) -> List[Dict]:
    """Every BENCH_r*/MULTICHIP_r* artifact, sorted by (kind, round)."""
    rounds = []
    for kind, pattern in _KINDS:
        for path in sorted(glob.glob(os.path.join(bench_dir, pattern))):
            m = _ROUND_RE.search(path)
            if not m:
                continue
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, ValueError) as e:
                rounds.append({"kind": kind, "round": -1, "path": path,
                               "rc": None, "degraded": f"unreadable: {e}",
                               "metrics": {}})
                continue
            rc = doc.get("rc")
            degraded = None
            if rc not in (0, None):
                degraded = f"rc={rc}"
            elif "parsed" in doc and doc.get("parsed") is None:
                degraded = "parsed: null"
            elif kind in _ONOFF_KINDS:
                bad = [q for q, row in (doc.get("queries") or {}).items()
                       if isinstance(row, dict)
                       and row.get("identical") is False]
                if bad:
                    degraded = f"non-identical results: {sorted(bad)}"
            extract = (extract_onoff_metrics if kind in _ONOFF_KINDS
                       else extract_metrics)
            rounds.append({
                "kind": kind,
                "round": int(m.group(1)),
                "path": path,
                "rc": rc,
                "degraded": degraded,
                # a degraded round contributes NO baselines: its numbers
                # (if any survived in the tail) are untrustworthy
                "metrics": {} if degraded else extract(doc),
            })
    rounds.sort(key=lambda r: (r["kind"], r["round"]))
    return rounds


def diff_rounds(rounds: List[Dict],
                threshold: float = 0.15) -> Tuple[List[Dict], List[str]]:
    """Walk rounds in order, comparing each tracked metric to the best
    prior value under the same name. Returns (regressions, notes)."""
    best: Dict[str, Tuple[float, str]] = {}  # name -> (value, round path)
    regressions: List[Dict] = []
    notes: List[str] = []
    for r in rounds:
        label = os.path.basename(r["path"])
        if r["degraded"]:
            notes.append(f"{label}: degraded round tolerated "
                         f"({r['degraded']}) — no metrics tracked")
            continue
        if not r["metrics"]:
            notes.append(f"{label}: no tracked metric lines")
            continue
        for name, value in sorted(r["metrics"].items()):
            if not _HIGHER_BETTER.search(name):
                continue
            prior = best.get(name)
            if prior is not None and value < prior[0] * (1.0 - threshold):
                regressions.append({
                    "metric": name,
                    "round": label,
                    "value": value,
                    "best_prior": prior[0],
                    "best_round": prior[1],
                    "drop_pct": round(100.0 * (1.0 - value / prior[0]), 1),
                })
            if prior is None or value > prior[0]:
                best[name] = (value, label)
    return regressions, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=".",
                    help="directory holding BENCH_r*/MULTICHIP_r* artifacts")
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="fractional drop vs best prior round that counts "
                         "as a regression (default 0.15)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full comparison as one JSON object")
    args = ap.parse_args(argv)

    if not os.path.isdir(args.dir):
        print(f"bench_diff: not a directory: {args.dir}", file=sys.stderr)
        return 2
    if not 0.0 < args.threshold < 1.0:
        print(f"bench_diff: threshold must be in (0, 1): {args.threshold}",
              file=sys.stderr)
        return 2
    rounds = load_rounds(args.dir)
    if not rounds:
        print(f"bench_diff: no BENCH_r*/MULTICHIP_r* artifacts under "
              f"{args.dir} — nothing to gate")
        return 0
    regressions, notes = diff_rounds(rounds, args.threshold)

    if args.json:
        print(json.dumps({
            "rounds": [{k: r[k] for k in
                        ("kind", "round", "rc", "degraded", "metrics")}
                       for r in rounds],
            "notes": notes,
            "regressions": regressions,
            "threshold": args.threshold,
        }, indent=1))
    else:
        for r in rounds:
            label = os.path.basename(r["path"])
            tracked = {n: v for n, v in r["metrics"].items()
                       if _HIGHER_BETTER.search(n)}
            if r["degraded"]:
                print(f"  {label}: DEGRADED ({r['degraded']})")
            else:
                cells = " ".join(f"{n}={v:g}" for n, v in sorted(
                    tracked.items())) or "(no tracked metrics)"
                print(f"  {label}: {cells}")
        for n in notes:
            print(f"  note: {n}")
    if regressions:
        for reg in regressions:
            print(f"bench_diff: REGRESSION {reg['metric']} in "
                  f"{reg['round']}: {reg['value']:g} is "
                  f"{reg['drop_pct']}% below best prior "
                  f"{reg['best_prior']:g} ({reg['best_round']})",
                  file=sys.stderr)
        return 1
    if not args.json:   # keep --json output one parseable object
        print(f"bench_diff: {len(rounds)} rounds clean "
              f"(threshold {args.threshold:.0%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
