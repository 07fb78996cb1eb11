"""Compile each configuration's scan_steps for a described (not attached)
TPU v5e at the real shapes and print memory_analysis(): what the chip's
compiler refuses, it refuses here at no chip time. Nothing runs, so this
says nothing about results or times and is never reported as a chip run.

    JAX_PLATFORMS=cpu python benchmarks/compile_v5e.py [--config NAME]

The batch's leaves and dtypes come from the program's own host staging of
one chunk (run on the CPU against a small table); the slab has the
configuration's pass_capacity.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def compile_config(cell_name: str, capacity: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run as bench
    from harness import reference, traffic
    from paddlebox_tpu.train.trainer import (make_dense_optimizer,
                                             make_train_step)

    spec = bench.load_cell(cell_name)
    cfg, cfg_mod = dict(spec["cfg"]), spec["cfg_mod"]
    if capacity:
        cfg["pass_capacity"] = capacity
    small = dict(cfg, occupied_rows=400_000, pass_capacity=1 << 19)
    mix = dict(spec["mix"], pool_files=8, files_per_pass=8, stride=1)
    tf = traffic.Traffic(small, mix, 1,
                         bench.trainer_config(small).scan_chunk)
    feed = traffic.feed_config(small)
    with tempfile.TemporaryDirectory() as tmp:
        pool, _ = tf.write_files(tmp)
        params0 = reference.init_params(cfg_mod.param_init(small), 1)
        trainer = bench.build_trainer(small, cfg_mod, feed, 1, params0)
        ds = traffic.make_dataset(feed, pool, tf.working_set())
        table = trainer.table
        table.begin_feed_pass()
        ds.load_into_memory(add_keys_fn=table.add_keys)
        table.end_feed_pass()
        table.begin_pass()
        group = ds.split_batches(num_workers=1)[0][:trainer.cfg.scan_chunk]
        staged = trainer._stack_batches_host(group)
        table.end_pass()
        opt_state = trainer.opt_state
        params = trainer.params
        layout = table.layout
        prng = table.next_prng()
        trainer.close()

    fns = make_train_step(
        cfg_mod.build_model(cfg), layout, bench.table_config(cfg),
        make_dense_optimizer(bench.trainer_config(cfg)), feed.batch_size,
        len(feed.used_sparse_slots()), True,
        compute_dtype=cfg["compute_dtype"], uid_write="scatter")

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one)
    slab = jax.ShapeDtypeStruct(
        (int(cfg["pass_capacity"]), layout.device_width),
        layout.device_dtype, sharding=one)
    args = (slab, jax.tree.map(sds, params), jax.tree.map(sds, opt_state),
            {k: sds(v) for k, v in staged.items()}, sds(prng))
    out = {"cell": cell_name, "config": cfg["name"],
           "slab_shape": list(slab.shape),
           "slab_logical_bytes": int(np.prod(slab.shape)) * 4,
           "batch_leaves": {k: [list(np.shape(v)), str(v.dtype)]
                            for k, v in staged.items()}}
    # the pass boundary's program: begin_pass permutes the whole resident
    # slab on the device (one promoted row, the smallest bucket)
    from paddlebox_tpu.embedding.pass_table import _delta_promote
    cap = int(cfg["pass_capacity"])
    promote = (slab,
               jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=one),
               jax.ShapeDtypeStruct((cap,), jnp.bool_, sharding=one),
               jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one),
               jax.ShapeDtypeStruct((1, layout.device_width),
                                    layout.device_dtype, sharding=one))
    for name, fn, a in (("scan_steps", fns.scan_steps, args),
                        ("delta_promote", _delta_promote, promote)):
        try:
            ma = fn.lower(*a).compile().memory_analysis()
        except Exception as e:     # what the chip's compiler would raise
            out[name] = {"refused": str(e).splitlines()[0][:300]}
            continue
        m = {f: int(getattr(ma, f + "_size_in_bytes", -1))
             for f in ("argument", "output", "alias", "temp")}
        m["peak_estimate_bytes"] = (m["argument"] + m["output"]
                                    - m["alias"] + m["temp"])
        out[name] = m
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None,
                    help="a configuration of BENCHMARK.json; default all")
    ap.add_argument("--capacity", type=int, default=0,
                    help="try another pass_capacity than the file's")
    args = ap.parse_args()
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    seen = set()
    for w in manifest["workloads"]:
        if w["config"] in seen or (args.config and w["config"]
                                   != args.config):
            continue
        seen.add(w["config"])
        print(json.dumps(compile_config(w["name"], args.capacity)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
