"""The comparison that decides `correct`, shown to fail.

1. The control: the reference put in the program's place with its matmul
   operands rounded to float8 (the step below the stated bfloat16) comes
   out as not correct against the configuration's own limits, at a size
   a test run can hold, on three seeds; so do the two planted faults.
2. The harness's run, without its look for a chip (--rehearse), with the
   timed path broken underneath, reports `correct` false: once for a step
   that returns its state unchanged, once for half of the batch left out
   with the mean taken over the rest. (One chip and no served answers:
   the cell has no exchange between chips and no token to alter.)

Not part of tier-1: python -m pytest benchmarks/tests -q
"""

import argparse
import dataclasses
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

CELL = "deepfm-criteo.micro-pass"


def _small(seed):
    import jax
    import run as bench
    from harness import reference, traffic
    spec = bench.load_cell(CELL)
    cfg = dict(spec["cfg"], **bench.REHEARSE)
    cfg["batch_size"] = 256
    steps = bench.trainer_config(cfg).scan_chunk
    tf = traffic.Traffic(cfg, spec["mix"], seed, steps)
    p0 = jax.device_get(reference.init_params(
        spec["cfg_mod"].param_init(cfg), seed))
    ex = tf.check

    def follow(**kw):
        return reference.follow(cfg, spec["cfg_mod"], p0, ex.rows, ex.labels,
                                ex.dense, steps, tf.table(), **kw)
    return cfg["limits"], follow


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 17, 12345])
def test_control_is_not_correct(seed):
    from harness import reference
    limits, follow = _small(seed)
    ref = follow()
    same = reference.compare(ref, ref)
    assert all(same[k] < 1e-12 for k in reference.GAPS), same
    for stand_in in ({"mm": reference.mm_control},
                     {"keep_half": True}, {"unchanged": True}):
        got = reference.compare(follow(**stand_in), ref)
        over = [k for k in limits if got[k] > limits[k]]
        assert over, (stand_in, got)


def _run_harness(capsys, seed=6):
    import run as bench
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.5,
                              trace=0, rehearse=True, control=0,
                              seeds="")
    assert bench.run(args) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _break_steps(monkeypatch, wrap):
    """Replace the trainer's step programs by wrap(original)."""
    from paddlebox_tpu.train import trainer as T
    make = T.make_train_step

    def broken(*a, **kw):
        fns = make(*a, **kw)
        return dataclasses.replace(fns, step=wrap(fns.step, False),
                                   scan_steps=wrap(fns.scan_steps, True))
    monkeypatch.setattr(T, "make_train_step", broken)


def test_sound_run_then_state_unchanged(capsys, monkeypatch):
    sound = _run_harness(capsys)
    assert sound["compared"]["count_mismatch"]["value"] == 0
    assert sound["compared"]["change_gap"]["value"] < 0.1

    def wrap(fn, _scan):
        def step(slab, params, opt_state, batch, prng):
            out = fn(slab + 0, params, opt_state, batch, prng)
            return (slab, params, opt_state) + tuple(out[3:])
        return step
    _break_steps(monkeypatch, wrap)
    out = _run_harness(capsys)
    assert out["correct"] is False
    assert out["compared"]["change_gap"]["value"] > 0.9
    assert out["compared"]["count_mismatch"]["value"] > 0


def test_half_of_the_batch_left_out(capsys, monkeypatch):
    import jax.numpy as jnp
    sound = _run_harness(capsys)

    def wrap(fn, scan):
        def step(slab, params, opt_state, batch, prng):
            v = batch["ins_valid"]
            half = v.shape[-1] // 2
            keep = jnp.arange(v.shape[-1]) < half
            batch = dict(batch, ins_valid=v & keep)
            return fn(slab, params, opt_state, batch, prng)
        return step
    _break_steps(monkeypatch, wrap)
    out = _run_harness(capsys)
    assert out["correct"] is False
    worse = [k for k in ("loss_gap", "grad_gap", "change_gap")
             if out["compared"][k]["value"]
             > 3 * sound["compared"][k]["value"]
             and out["compared"][k]["value"]
             > out["compared"][k]["limit"]]
    assert worse, (sound["compared"], out["compared"])
