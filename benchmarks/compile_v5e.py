"""Compile each configuration's scan_steps for a described (not attached)
TPU v5e at the real shapes and print memory_analysis(): what the chip's
compiler refuses, it refuses here at no chip time. Nothing runs, so this
says nothing about results or times and is never reported as a chip run.
That compiler refuses temporaries that do not fit; it does not add the
arguments and outputs to them (18 GB of outputs compiled here, PR 29), so
hold `peak_estimate_bytes` against `limit_bytes` yourself: a program over
it compiles here and is refused its memory when it runs.

    JAX_PLATFORMS=cpu python benchmarks/compile_v5e.py [--config NAME]
    JAX_PLATFORMS=cpu python benchmarks/compile_v5e.py --cell seq-probe.seq-pass

The batch's leaves and dtypes come from the program's own host staging of
one chunk (run on the CPU against a table no larger than the
configuration's own); the slab has the configuration's pass_capacity.

    JAX_PLATFORMS=cpu python benchmarks/compile_v5e.py --reference \
        --params 6.03e8 --rows 25024 --embedx 2048 --examples 4 --slots 4096

compiles the REFERENCE's step (harness/reference._step) the same way, at
shapes no configuration has yet: what a next configuration's check pass
may be sized to (README, "What the reference needs"; --rows is the
smaller of the occurrences of a scan chunk and the vocabulary, as
reference.follow pads its table). The dense
parameters are a stand-in stack of matmuls over `pooled`, each layer
under jax.checkpoint, as a configuration's forward may block itself.
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

LIMIT_BYTES = int(15.75 * 2 ** 30)      # what one program may hold on a v5e


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
    # the CPU stand-in's table: no larger than the configuration's own (a
    # wide row makes a 1 << 19 slab gigabytes on the host)
    small = dict(cfg,
                 occupied_rows=min(400_000, int(cfg["occupied_rows"])),
                 pass_capacity=min(1 << 19, int(cfg["pass_capacity"])))
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
    # the pass boundary's program: begin_pass scatters the arrived keys'
    # rows into the resident slab in place (one row, the smallest bucket)
    from paddlebox_tpu.embedding.pass_table import _delta_promote
    promote = (slab,
               jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one),
               jax.ShapeDtypeStruct((1, layout.device_width),
                                    layout.device_dtype, sharding=one))
    out["scan_steps"] = memory_of(fns.scan_steps, args)
    out["delta_promote"] = memory_of(_delta_promote, promote)
    out["limit_bytes"] = LIMIT_BYTES
    return out


def memory_of(fn, args, **static) -> dict:
    """memory_analysis() of fn compiled for the arguments' (described)
    device, or what its compiler refused it with."""
    try:
        ma = fn.lower(*args, **static).compile().memory_analysis()
    except Exception as e:     # what the chip's compiler would raise
        return {"refused": str(e).splitlines()[0][:300]}
    m = {f: int(getattr(ma, f + "_size_in_bytes", -1))
         for f in ("argument", "output", "alias", "temp")}
    m["peak_estimate_bytes"] = (m["argument"] + m["output"]
                                - m["alias"] + m["temp"])
    return m


REFERENCE_LAYERS = 5    # pairs of matmuls in --reference's stand-in tower


def compile_reference(params: float, rows: int, embedx: int, examples: int,
                      slots: int) -> dict:
    """The reference's _step for a described v5e at the given shapes: a
    table of ``rows`` touched rows with ``embedx`` floats of embedding,
    ``examples`` x ``slots`` positions a step, and about ``params`` dense
    parameters as REFERENCE_LAYERS pairs of matmuls [3 + embedx, F],
    [F, 3 + embedx] over pooled, each pair under jax.checkpoint."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import reference

    width, layers = 3 + embedx, REFERENCE_LAYERS
    hidden = max(1, int(round(params / (2.0 * width * layers))))

    def forward(p, pooled, dense, mm):
        @jax.checkpoint
        def layer(h, up, down):
            return h + mm(jax.nn.relu(mm(h, up)), down)
        h = pooled
        for i in range(layers):
            h = layer(h, p["up%d" % i], p["down%d" % i])
        return h.mean(axis=(1, 2))

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    p = {}
    for i in range(layers):
        p["up%d" % i] = sds((width, hidden))
        p["down%d" % i] = sds((hidden, width))
    adam = {"t": sds(()), "mu": dict(p), "nu": dict(p)}
    table = {"show": sds((rows,)), "click": sds((rows,)), "w": sds((rows,)),
             "w_g2": sds((rows,)), "x": sds((rows, embedx)),
             "x_g2": sds((rows,)), "mf": sds((rows,), jnp.bool_)}
    args = (p, adam, table, sds((examples, slots), jnp.int32),
            sds((examples,), jnp.uint8), sds((examples, 0)),
            sds((examples,), jnp.bool_))
    cfg = {"dense_lr": 1e-3, "sparse_learning_rate": 0.05,
           "mf_create_thresholds": 0.0}
    return {"dense_params": 2 * width * hidden * layers, "layers": layers,
            "hidden": hidden, "rows": rows, "embedx": embedx,
            "positions": [examples, slots],
            "memory": memory_of(
                reference._step, (forward, reference.mm_f32, rows,
                                  reference.sparse_settings(cfg)) + args),
            "limit_bytes": LIMIT_BYTES}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None,
                    help="a configuration of BENCHMARK.json; default all")
    ap.add_argument("--cell", default=None,
                    help="one cell, or a <config>.<mix> that runs by name")
    ap.add_argument("--capacity", type=int, default=0,
                    help="try another pass_capacity than the file's")
    ap.add_argument("--reference", action="store_true",
                    help="compile the reference's step at the shapes below")
    ap.add_argument("--params", type=float, default=5.2e8)
    ap.add_argument("--rows", type=int, default=131072,
                    help="the reference's table: min(scan_chunk x examples "
                         "x slots, occupied_rows), as reference.follow pads")
    ap.add_argument("--embedx", type=int, default=2048)
    ap.add_argument("--examples", type=int, default=8)
    ap.add_argument("--slots", type=int, default=2048)
    args = ap.parse_args()
    if args.reference:
        print(json.dumps(compile_reference(
            args.params, args.rows, args.embedx, args.examples,
            args.slots)), flush=True)
        return 0
    if args.cell:
        print(json.dumps(compile_config(args.cell, args.capacity)),
              flush=True)
        return 0
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
