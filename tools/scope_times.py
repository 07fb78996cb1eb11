"""Device seconds per jax.named_scope per program, from a profiler trace.

    python tools/scope_times.py <trace.xplane.pb> <snapshot.json>

A device trace names an operation by its HLO instruction (``%fusion.206``),
not by the scope it was traced under. ``obs.device.snapshot()`` carries,
per instrumented jit entry, ``module`` (the XLA module's name, which the
trace's ``XLA Modules`` line repeats) and ``scopes`` ({instruction:
scope}); this joins the two. <snapshot.json> is ``json.dump`` of that
snapshot, taken in the process that was traced - one that COMPILED its
programs (``JAX_COMPILATION_CACHE_DIR`` at an empty directory): jax keys a
cached program without its metadata, so a cache filled before a scope
existed hands back an executable whose operations all read "(no scope)".
Operations that only contain others (while, conditional, call) are left
out, as trace_reduce.top_ops leaves them out: their bodies' are counted.
"""

from __future__ import annotations

import bisect
import json
import os
import sys
from typing import Dict

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from harness import trace_reduce as tr  # noqa: E402

NO_SCOPE = "(no scope)"       # in the map, under none of the named scopes
NOT_IN_MAP = "(not in map)"   # the snapshot has no such program/instruction


def scope_times(trace: dict, snapshot: dict) -> Dict[str, Dict[str, float]]:
    """{program: {scope: device seconds}}, a mean over the device planes."""
    maps = {e["module"]: e["scopes"]
            for e in snapshot["entries"].values() if "scopes" in e}
    out: Dict[str, Dict[str, float]] = {}
    n_dev = max(1, len(trace["devices"]))
    for lines in trace["devices"].values():
        mods = sorted((s, e, n.split("(")[0])
                      for n, s, e in lines.get(tr.MODULES_LINE, []))
        starts = [m[0] for m in mods]
        for name, s, e in lines.get(tr.OPS_LINE, []):
            op, code = tr.short_name(name)
            if code in tr.CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "(no program)"
            scope = maps.get(prog, {}).get(op.lstrip("%"), NOT_IN_MAP)
            acc = out.setdefault(prog, {})
            acc[scope or NO_SCOPE] = (acc.get(scope or NO_SCOPE, 0.0)
                                      + (e - s) / n_dev)
    return out


def main(argv) -> int:
    if len(argv) != 3:
        print(__doc__, file=sys.stderr)
        return 2
    with open(argv[2]) as f:
        snapshot = json.load(f)
    times = scope_times(tr.load(argv[1]), snapshot)
    for prog, acc in sorted(times.items(), key=lambda kv: -sum(kv[1].values())):
        whole = sum(acc.values())
        unnamed = acc.get(NO_SCOPE, 0.0) + acc.get(NOT_IN_MAP, 0.0)
        print("%s  %.6f s, %.2f%% in no named scope"
              % (prog, whole, 100.0 * unnamed / whole if whole else 0.0))
        for scope, secs in sorted(acc.items(), key=lambda kv: -kv[1]):
            print("  %-16s %12.6f s  %6.2f%%" % (scope, secs,
                                                 100.0 * secs / whole))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
