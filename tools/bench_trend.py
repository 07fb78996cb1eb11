"""Bench trajectory: cross-round deltas of the headline BENCH rates.

Reads every ``BENCH_r*.json`` under ``--root`` (the ``{"n", "parsed"}``
envelope bench.py writes where ``PBTPU_BENCH_OUT`` points — it writes
nothing into the checkout by itself, so the series is one the caller
collects), orders them by round number, and prints one line per headline
metric per consecutive pair: absolute values, the delta, and a REGRESSION
flag when a higher-is-better rate drops (or ms/step rises) by more than
``--threshold`` (default 10%).

Honesty guards: rounds on different platforms are never compared — the
platform column makes the tier visible; zero/absent values (failed rounds,
pre-round fields) compare as "n/a" rather than as infinite regressions.

Usage:
    python tools/bench_trend.py [--root PATH] [--threshold 0.10] [--json]

Exit code 1 when any flagged regression exists (CI-pluggable), else 0.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

#: (record key, unit, higher_is_better)
HEADLINES: List[Tuple[str, str, bool]] = [
    ("value", "ex/s", True),
    ("e2e_examples_per_sec", "ex/s", True),
    ("e2e_lean", "ex/s", True),
    ("pass_amortized_examples_per_sec", "ex/s", True),
    ("steady_ms_per_step", "ms", False),
    # round-15 checkpoint plane (store-level columnar save/load; absent
    # pre-round-15 rounds compare as n/a, not as regressions)
    ("ckpt_save_keys_per_sec", "keys/s", True),
    ("ckpt_load_keys_per_sec", "keys/s", True),
    # round-17 ingest plane: the cold-pass parse→shuffle→pack→train
    # headline (absent pre-round-17 rounds compare as n/a)
    ("ingest_cold_pass_examples_per_sec", "ex/s", True),
    # round-16 SSD tier (landed after 17 — absent earlier rounds
    # compare as n/a): the feed-pass promote leg and the lookup-path
    # cold fault over spilled rows
    ("ssd_promote_keys_per_sec", "keys/s", True),
    ("ssd_fault_keys_per_sec", "keys/s", True),
    # round-20 device plane: the compiled step's bytes-accessed per
    # example (Tensor Casting's co-design metric, from the one-time
    # cost-analysis snapshot). LOWER is better — a rise past the
    # threshold is a byte-budget regression and flags exactly like a
    # rate regression (absent pre-round-20 rounds compare as n/a)
    ("device_bytes_accessed_per_example", "B/ex", False),
    # round-21 serving fleet: the multi-box ladder's top-rung
    # client-side pull rate (tools/fleet_probe.py; absent pre-round-21
    # rounds compare as n/a)
    ("fleet_pull_keys_per_sec", "keys/s", True),
    # round-19 streaming plane (landed after 21 — absent earlier rounds
    # compare as n/a): sustained micro-pass rate, and the drop-to-
    # journal-poll freshness where LOWER is better — a rise past the
    # threshold is a staleness regression
    ("streaming_examples_per_sec", "ex/s", True),
    ("streaming_freshness_secs", "s", False),
    # round-20 watermark plane (landed after 21/22 — absent earlier
    # rounds compare as n/a): fleet-wide answered-pull QPS from the
    # fleet probe's top rung, and the TRUE feed-to-serve freshness p99
    # (born-ts -> watermark-stamped pull through a live server) where
    # LOWER is better — a rise is a staleness regression
    ("fleet_qps", "q/s", True),
    ("freshness_e2e_p99_secs", "s", False),
]


def load_rounds(root: str) -> List[Dict[str, Any]]:
    out = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.match(r"BENCH_r(\d+)\.json$", os.path.basename(path))
        if not m:
            continue
        try:
            with open(path, encoding="utf-8") as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        rec = doc.get("parsed") if isinstance(doc.get("parsed"), dict) \
            else doc
        out.append({"round": int(m.group(1)),
                    "path": os.path.basename(path),
                    "schema_version": doc.get("schema_version", 1),
                    "platform": rec.get("platform", "?"),
                    "record": rec})
    out.sort(key=lambda r: r["round"])
    return out


def _num(rec: dict, key: str) -> Optional[float]:
    v = rec.get(key)
    if isinstance(v, (int, float)) and v > 0:
        return float(v)
    return None


def trend(rounds: List[Dict[str, Any]], threshold: float) -> dict:
    rows = []
    regressions = []
    for prev, cur in zip(rounds, rounds[1:]):
        pr, cr = prev["record"], cur["record"]
        comparable = (prev["platform"] == cur["platform"]
                      and prev["platform"] != "?")
        for key, unit, hib in HEADLINES:
            a, b = _num(pr, key), _num(cr, key)
            row = {"metric": key, "unit": unit,
                   "from_round": prev["round"], "to_round": cur["round"],
                   "platform": cur["platform"],
                   "from": a, "to": b}
            if a is None or b is None or not comparable:
                row["delta_pct"] = None
            else:
                delta = (b - a) / a
                row["delta_pct"] = round(100.0 * delta, 1)
                regressed = ((-delta if hib else delta) > threshold)
                row["regression"] = regressed
                if regressed:
                    regressions.append(row)
            rows.append(row)
    return {"rows": rows, "regressions": regressions}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="print cross-round BENCH deltas for the headline "
                    "rates; flag regressions past the threshold")
    ap.add_argument("--root",
                    default=os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))),
                    help="directory holding the BENCH_r*.json series")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="regression flag threshold as a fraction "
                         "(default 0.10 = 10%%)")
    ap.add_argument("--json", action="store_true",
                    help="one JSON document instead of the table")
    args = ap.parse_args(argv)

    rounds = load_rounds(args.root)
    if len(rounds) < 2:
        print(json.dumps({"error": "need >=2 BENCH_r*.json rounds",
                          "found": [r["path"] for r in rounds]}))
        return 0
    result = trend(rounds, args.threshold)
    if args.json:
        print(json.dumps({"rounds": [
            {k: r[k] for k in ("round", "path", "platform",
                               "schema_version")} for r in rounds],
            **result}))
    else:
        print("round series: " + " -> ".join(
            "r%d[%s]" % (r["round"], r["platform"]) for r in rounds))
        for row in result["rows"]:
            if row["from"] is None and row["to"] is None:
                continue
            def fmt(v):
                return "%.1f" % v if v is not None else "n/a"
            mark = ("  REGRESSION" if row.get("regression")
                    else "" if row["delta_pct"] is None else "")
            delta = ("%+.1f%%" % row["delta_pct"]
                     if row["delta_pct"] is not None else "  n/a")
            print("r%02d->r%02d  %-34s %10s -> %10s  %8s%s"
                  % (row["from_round"], row["to_round"],
                     "%s (%s)" % (row["metric"], row["unit"]),
                     fmt(row["from"]), fmt(row["to"]), delta, mark))
        if result["regressions"]:
            print("%d regression(s) past %.0f%%"
                  % (len(result["regressions"]), 100 * args.threshold))
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
