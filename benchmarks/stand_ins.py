"""python benchmarks/stand_ins.py --workload <cell> --seeds a,b,c [--only control_fp8,fault_half_batch]

What `run.py --control 2` reads, for a configuration whose reference step
fills the chip: for each seed the float32 reference of the check pass,
then the control (every matmul operand rounded to float8) and the planted
faults (half of every batch left out; a state left unchanged), each put in
the program's place and judged under the configuration's committed
`limits` by run.py's own `Run.follow` and `Run.judge`.

`run.py --control 2` keeps the reference's compiled step loaded while the
next stand-in's loads, and two of them do not fit beside 16 B a parameter
at 602.9M ("Attempting to reserve 7.64G at the bottom of memory ... 1.89G
free", my chip runs, PR 34). Dropping the programs inside one process
(`jax.clear_caches()`) freed the chip in one run and not in the next, so
here every reading is a PROCESS of its own: one follows the reference and
leaves it in a file, one a stand-in reads it back, and a process that has
ended holds nothing. This process never touches jax: the chip belongs to
one process at a time. Each child makes the seed's traffic again (35-70 s
at the cell's size).

One JSON line a seed, in the form of `run.py --control 2`, and the same
lines appended to chiprun_out/stand_ins.jsonl; exit 1 where a stand-in
read `correct: true`. `--rehearse` walks it on a CPU.
"""
import argparse
import json
import os
import pickle
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
STAND_INS = ("control_fp8", "fault_half_batch", "fault_state_unchanged")


def child(args) -> int:
    """One reading: the reference into --ref, or --stand-in against it."""
    sys.path.insert(0, HERE)
    import run as bench
    from harness import reference
    from paddlebox_tpu.utils.platform import ensure_compile_cache
    ensure_compile_cache()
    kinds = {"control_fp8": {"mm": reference.mm_control},
             "fault_half_batch": {"keep_half": True},
             "fault_state_unchanged": {"unchanged": True}}
    r = bench.Run(argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=0.0, trace=0,
        rehearse=args.rehearse, control=2, seeds=""), args.seed)
    r.new_compiles = {}
    r.make_traffic()
    r.start = r.tf.table()
    if not args.stand_in:
        with open(args.ref, "wb") as f:
            pickle.dump(r.follow(), f)
        return 0
    with open(args.ref, "rb") as f:
        ref = pickle.load(f)
    got = r.follow(**kinds[args.stand_in])
    compared, _limits, correct = r.judge(got, ref, {})
    print(json.dumps(dict(bench.strip(compared), correct=correct,
                          loss=got["loss"])), flush=True)
    return 0


def read(args, seed: int, ref: str, stand_in: str = ""):
    cmd = [sys.executable, os.path.abspath(__file__), "--workload",
           args.workload, "--seed", str(seed), "--ref", ref]
    cmd += ["--stand-in", stand_in] if stand_in else []
    cmd += ["--rehearse"] if args.rehearse else []
    done = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
    if done.returncode:
        raise SystemExit("seed %d %s: exit %d"
                         % (seed, stand_in or "reference", done.returncode))
    return json.loads(done.stdout.splitlines()[-1]) if stand_in else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--only", default=",".join(STAND_INS))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int)             # a child's
    ap.add_argument("--ref")
    ap.add_argument("--stand-in", default="", choices=("",) + STAND_INS)
    args = ap.parse_args()
    if args.ref:
        return child(args)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    all_false = True
    with open(os.path.join(ROOT, "chiprun_out", "stand_ins.jsonl"),
              "a") as out, tempfile.TemporaryDirectory() as tmp:
        for seed in [int(s) for s in args.seeds.split(",")]:
            ref = os.path.join(tmp, "ref-%d.pkl" % seed)
            read(args, seed, ref)
            verdicts = {name: read(args, seed, ref, name)
                        for name in args.only.split(",") if name}
            os.remove(ref)
            all_false &= not any(v["correct"] for v in verdicts.values())
            line = json.dumps({"seed": seed, "workload": args.workload,
                               "controls": verdicts})
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()
    return 0 if all_false else 1


if __name__ == "__main__":
    sys.exit(main())
