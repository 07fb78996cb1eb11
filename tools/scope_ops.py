"""The device operations under named scopes in one benchmark cell's traced
run: each operation's ms a step and what its HLO holds (a scatter, a
gather, a sort, a kernel), beside the run's own result line.

    python tools/scope_ops.py --root <checkout> --scopes moe_route -- \\
        --workload trinity-mini.seq-pass --seed 1 --seconds 51

Runs <checkout>/benchmarks/run.py in this process with ``--trace 1`` (the
checkout's own program and benchmark, so a parent commit's checkout reads
as well as this one) and prints its result line unchanged. Before the run
drops its trace, the step programs' operations (``scan_steps``,
``_step_impl``, ``train_step``) are joined with the scope maps
(obs/device.py) and with the compiled modules' HLO text: an operation
under one of ``--scopes`` is listed with its device seconds in the
traced window and the opcodes it holds (a fusion's called computations
included). ms a step = an operation's share of the step programs' device
time x the run's ``train_step.device_ms_per_step``. Writes
<out>/scope_ops-<workload>-<seed>.json (--out, the working directory by
default) and a summary on standard error.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import io
import json
import os
import re
import sys
from typing import Dict, List, Set

STEP_PROGRAMS = re.compile(r"scan_steps|_step_impl|train_step")
# opcodes worth naming in an operation's contents
NOTED = ("scatter", "gather", "sort", "custom-call", "dot", "convolution",
         "dynamic-update-slice", "dynamic-slice", "reduce")
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)"
                    r"=\{?([%\w.\-, ]+)\}?")


def contents(hlo_texts: List[str]) -> Dict[str, List[str]]:
    """{instruction (no '%'): the NOTED opcodes it runs, its called
    computations' included}, over every module text."""
    own: Dict[str, str] = {}
    calls: Dict[str, List[str]] = {}
    comp_ops: Dict[str, Set[str]] = {}
    comp_calls: Dict[str, List[str]] = {}
    for text in hlo_texts:
        comp = None
        for line in text.splitlines():
            m = _COMP.match(line)
            if m and " = " not in line:
                comp = m.group(1)
                comp_ops.setdefault(comp, set())
                comp_calls.setdefault(comp, [])
                continue
            m = _INSTR.match(line)
            if m is None:
                continue
            name = m.group(1)
            rest = line[m.end():]
            op = _OPCODE.search(" " + rest)
            code = op.group(1) if op else ""
            called = [c.strip().lstrip("%") for g in _CALLS.findall(rest)
                      for c in g.split(",") if c.strip()]
            own[name], calls[name] = code, called
            if comp is not None:
                comp_ops[comp].add(code)
                comp_calls[comp].extend(called)

    def ops_of(comp, seen):
        if comp in seen:
            return set()
        seen.add(comp)
        out = set(comp_ops.get(comp, ()))
        for c in comp_calls.get(comp, ()):
            out |= ops_of(c, seen)
        return out
    out = {}
    for name, code in own.items():
        ops = {code}
        for c in calls[name]:
            ops |= ops_of(c, set())
        out[name] = sorted(o for o in ops if o in NOTED)
    return out


def scope_ops(trace: dict, window, maps: dict, held: Dict[str, List[str]],
              scopes: Set[str], tr) -> dict:
    """{'step_s': device seconds of the step programs' operations in the
    window, 'ops': [[scope, instruction, seconds, contents]]}:
    trace_reduce.scope_times over maps that name each operation of
    ``scopes`` apart."""
    apart = {prog: {op: (scope + "/" + op if scope in scopes else scope)
                    for op, scope in m.items()} for prog, m in maps.items()}
    acc: Dict[str, float] = {}
    step_s = 0.0
    for prog, times in tr.scope_times(trace, apart, window).items():
        if STEP_PROGRAMS.search(prog):
            step_s += sum(times.values())
            for key, secs in times.items():
                if "/" in key:
                    acc[key] = acc.get(key, 0.0) + secs
    ops = [key.split("/", 1) + [secs, held.get(key.split("/", 1)[1], [])]
           for key, secs in sorted(acc.items(), key=lambda kv: -kv[1])]
    return {"step_s": step_s, "ops": ops}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=".")
    ap.add_argument("--scopes", default="moe_route")
    ap.add_argument("--out", default=".")
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    out_dir = os.path.abspath(args.out)
    root = os.path.abspath(args.root)
    rest = [a for a in args.rest if a != "--"]
    bench = os.path.join(root, "benchmarks")
    sys.path[:0] = [root, bench]
    os.chdir(root)
    from paddlebox_tpu.obs import device as obs_device
    from harness import trace_reduce as tr

    texts: List[str] = []
    real_map = obs_device.scope_map

    def keep_text(text):
        texts.append(text)
        return real_map(text)
    obs_device.scope_map = keep_text

    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(bench, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    found: dict = {}
    real_top = tr.top_ops
    scopes = set(args.scopes.split(","))

    def top_ops(trace, k=10, window=None):
        found.update(scope_ops(trace, window or tr.window_of(trace),
                               run.scope_maps(), contents(texts), scopes,
                               tr))
        return real_top(trace, k, window)
    tr.top_ops = top_ops

    sys.argv = ["run.py"] + rest + ["--trace", "1"]
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main()
    printed = buf.getvalue()
    sys.stdout.write(printed)
    line = json.loads(printed.strip().splitlines()[-1]) if printed else {}
    step_ms = line.get("metrics", {}).get(
        "train_step.device_ms_per_step", {}).get("value")
    if found and step_ms:
        for row in found["ops"]:
            row.append(row[2] / found["step_s"] * step_ms)
    wl = rest[rest.index("--workload") + 1] if "--workload" in rest else "?"
    seed = rest[rest.index("--seed") + 1] if "--seed" in rest else "?"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "scope_ops-%s-%s.json" % (wl, seed))
    with open(path, "w") as f:
        json.dump({"root": root, "workload": wl, "seed": seed,
                   "device_ms_per_step": step_ms, **found}, f)
    by: Dict[str, float] = {}
    for row in found.get("ops", []):
        if len(row) > 4:
            kind = ("scatter" if "scatter" in row[3] else
                    "sort" if "sort" in row[3] else
                    "gather" if "gather" in row[3] else
                    "kernel" if "custom-call" in row[3] else "other")
            by[row[0] + "." + kind] = by.get(row[0] + "." + kind, 0) + row[4]
    print("scope_ops %s seed %s: ms a step by kind %s; top %s" % (
        wl, seed, json.dumps({k: round(v, 3) for k, v in by.items()}),
        json.dumps([[r[1], round(r[4], 3), r[3]]
                    for r in found.get("ops", [])[:25] if len(r) > 4])),
        file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
