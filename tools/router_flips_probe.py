"""Which held (token, expert) pairs does the program choose and the plain
reference not, or the other way round? Forward only, from a seed's starting
weights and rows, each batch of the cell's check pass: the program's layers
in bfloat16 beside benchmarks/configs/nemotron-3-super.py's in float32,
layer by layer; before every LatentMoE layer the two sides' choices among
the held experts, by expert, how near the threshold (a token's 22nd score)
the pairs that differ lie, and how far apart the two residual streams are.

    chiprun -- python tools/router_flips_probe.py --seed 3000041131
    JAX_PLATFORMS=cpu python tools/router_flips_probe.py --seed 7 --rehearse

Written for PERF.md section 6, PR 41 (seed 3000041131's grad_gap 0.0856 on
layer 8's routed path): ~95 s on the chip for eight batches; the whole
record goes to --out.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import run as bench  # noqa: E402
from harness import reference  # noqa: E402
from paddlebox_tpu.models.afmoe import rms_norm  # noqa: E402
from paddlebox_tpu.ops.routed_experts import route  # noqa: E402

KEPT = ("ref_pairs", "program_pairs", "flipped", "share",
        "flipped_abs_margin_median", "abs_margin_median",
        "pairs_within_1e-3_of_threshold", "h_error")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="nemotron-3-super.seq-pass-2x4096")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out")
    a = ap.parse_args()
    r = bench.Run(argparse.Namespace(
        workload=a.workload, seed=a.seed, seconds=51.0, trace=0,
        rehearse=a.rehearse, control=0, seeds=""))
    cfg, mod = r.cfg, r.cfg_mod
    off, held_n, top_k = (cfg["expert_offset"], cfg["n_routed_experts"],
                          cfg["num_experts_per_tok"])
    pattern = cfg["hybrid_override_pattern"]
    params = r.make_traffic()                  # float32, on the device
    r.start = r.tf.table()
    rows = r.tf.check.rows
    B = rows.shape[0] // r.check_steps
    model = mod.build_model(cfg)
    mm = reference.mm_f32
    first = {k: pattern.index(k) for k in set(pattern)}
    ref_layer = {k: jax.jit(lambda p, h, i=i: mod._layer(cfg, i, mm, p, h))
                 for k, i in first.items()}
    prog_layer = {k: jax.jit(lambda p, h, i=i: model._layer(
        i, p, h, jnp.bfloat16)[0]) for k, i in first.items()}

    @jax.jit
    def ref_choice(p, h):                       # one example [S, H]
        u = mod._norm(h, p["norm"], cfg["norm_eps"])
        biased = jax.nn.sigmoid(mm(u, p["router_w"])) + p["router_b"]
        kth = jnp.sort(biased, axis=-1)[..., -top_k]
        held = biased[:, off:off + held_n]
        return held >= kth[:, None], held - kth[:, None]

    @jax.jit
    def prog_choice(p, h):                      # [B, S, H]
        u = rms_norm(h, p["norm"], cfg["norm_eps"])
        experts, _ = route(u.reshape(-1, u.shape[-1]), p["router_w"],
                           p["router_b"], top_k,
                           cfg["routed_scaling_factor"])
        return (experts[:, :, None] == off + jnp.arange(held_n)).any(axis=1)

    out = {"seed": r.seed, "batches": []}
    t0 = time.perf_counter()
    for s in range(min(a.batches, r.check_steps)):
        x = jnp.asarray(r.start["x"][rows[s * B:(s + 1) * B]], jnp.float32)
        hr, hp = x, x
        rec = {}
        for i, kind in enumerate(pattern):
            pre = "l%d." % i
            p = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            if kind == "E":
                got = [ref_choice(p, hr[b]) for b in range(B)]
                rb = np.concatenate([np.asarray(g[0]) for g in got])
                margin = np.concatenate([np.asarray(g[1]) for g in got])
                pb = np.asarray(prog_choice(p, hp))
                fl = rb ^ pb
                rec[str(i)] = {
                    "ref_pairs": int(rb.sum()),
                    "program_pairs": int(pb.sum()),
                    "flipped": int(fl.sum()),
                    "share": float(fl.sum() / max(rb.sum(), 1)),
                    "flipped_by_expert": fl.sum(axis=0).tolist(),
                    "ref_by_expert": rb.sum(axis=0).tolist(),
                    "program_by_expert": pb.sum(axis=0).tolist(),
                    "flipped_abs_margin_median": float(np.median(
                        np.abs(margin[fl]))) if fl.any() else None,
                    "abs_margin_median": float(np.median(np.abs(margin))),
                    "pairs_within_1e-3_of_threshold": int(
                        (np.abs(margin) < 1e-3).sum()),
                    "h_error": float(jnp.linalg.norm(hp - hr)
                                     / jnp.linalg.norm(hr))}
            hr = jnp.stack([ref_layer[kind](p, hr[b]) for b in range(B)])
            hp = prog_layer[kind](p, hp)
        rec["h_error_end"] = float(jnp.linalg.norm(hp - hr)
                                   / jnp.linalg.norm(hr))
        out["batches"].append(rec)
        bench.log("batch %d done (%.1f s)" % (s, time.perf_counter() - t0))
        print("FLIPS", s, json.dumps({
            k: ({q: v[q] for q in KEPT} if isinstance(v, dict) else v)
            for k, v in rec.items()}), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
