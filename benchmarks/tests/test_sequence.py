"""The generator makes a sequence, a configuration rehearses at its own
sizes, and the reference's table is no larger than the vocabulary:

1. table_sizes returns today's integers for the two configurations in the
   tree, returns at any slot count for which the tables fit (it died of a
   float overflow at 104), and still refuses a table set that cannot fit.
2. "slot_tables": "shared" puts one table under every slot: the draw
   stays inside it, an example holds a key more than once, and
   expected_counts counts every occurrence.
3. --rehearse applies REHEARSE and then the configuration's `rehearse`.
4. follow() pads its table to min(rows.size, occupied_rows), and what it
   returns is the same either way, bit for bit.
5. The probe's files walk run.py --rehearse to `correct: true` on the CPU.

Not part of tier-1: python -m pytest benchmarks/tests -q
"""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from test_reference import _assert_same  # noqa: E402

PROBE = "seq-probe.seq-pass"
# (slots, occupied, smallest) -> first three sizes, last three, sha256 of
# the int64 bytes: what the generator gave before it learned sequences
FROZEN = {
    (39, 12_500_000, 10): ([10, 13, 19], [1822159, 2551019, 3571443],
                           "604f21e83dbeaa59"),
    (26, 5_000_000, 10): ([10, 16, 26], [727902, 1184316, 1926923],
                          "ff2e657367e71db6"),
    (39, 20_000, 10): ([10, 11, 13], [2051, 2378, 2779],
                       "6cf703ffe49cb829"),
    (103, 100_000, 10): ([10, 10, 11], [5382, 5732, 6150],
                         "d033c3906c2038b0"),
}


@pytest.mark.parametrize("args", sorted(FROZEN))
def test_table_sizes_are_the_frozen_integers(args):
    from harness.traffic import table_sizes
    first, last, digest = FROZEN[args]
    sizes = table_sizes(*args)
    assert sizes.dtype == np.int64 and sizes.sum() == args[1]
    assert sizes[:3].tolist() == first and sizes[-3:].tolist() == last
    assert hashlib.sha256(sizes.tobytes()).hexdigest()[:16] == digest


@pytest.mark.parametrize("slots, occupied", [
    (104, 100_000), (4096, 100_000), (8192, 100_000), (4096, 12_500_000),
    (4096, 40_960), (1, 25_024)])
def test_table_sizes_return_wherever_the_tables_fit(slots, occupied):
    from harness.traffic import table_sizes
    sizes = table_sizes(slots, occupied, 10)
    assert sizes.shape == (slots,) and sizes.sum() == occupied
    assert sizes.min() >= 1 and (np.diff(sizes[:-1]) >= 0).all()


def test_too_few_rows_is_still_the_error():
    from harness.traffic import table_sizes
    with pytest.raises(ValueError, match="occupied rows too few"):
        table_sizes(4096, 20_000, 10)


@pytest.fixture(scope="module")
def probe():
    import run as bench
    spec = bench.load_cell(PROBE)
    return spec, bench.rehearsal(spec["cfg"])


def test_rehearse_keys_override_the_harness(probe):
    import run as bench
    spec, cfg = probe
    own = spec["cfg"]["rehearse"]
    assert own and all(cfg[k] == v for k, v in own.items())
    assert own["batch_size"] != bench.REHEARSE["batch_size"]
    assert cfg["pass_capacity"] == bench.REHEARSE["pass_capacity"]
    assert spec["cfg"]["num_sparse_slots"] == 4096     # the file's own stay
    # a configuration without the object rehearses as it did
    deepfm = bench.load_cell("deepfm-criteo.micro-pass")["cfg"]
    assert "rehearse" not in deepfm
    assert bench.rehearsal(deepfm) == {**deepfm, **bench.REHEARSE}


def test_a_shared_table_lies_under_every_slot(probe):
    from harness import traffic
    spec, cfg = probe
    tf = traffic.Traffic(cfg, spec["mix"], 2 ** 31 + 17, 8)
    S, occupied = cfg["num_sparse_slots"], cfg["occupied_rows"]
    assert tf.sizes.tolist() == [occupied] and tf.offsets.tolist() == [0]
    rows = tf.check.rows
    assert rows.shape == (8 * cfg["batch_size"], S)
    assert rows.min() >= 0 and rows.max() < occupied
    # an example holds its hot keys more than once, every slot draws from
    # the whole table, and the table's slot column is 0
    assert all(np.unique(r).size < S for r in rows)
    assert np.unique(rows[:, 0]).size > 1 and np.unique(rows[:, -1]).size > 1
    assert (np.ptp(rows, axis=0) > occupied // 4).sum() > S // 2
    table = tf.table()
    assert table["slot"].shape == (occupied,) and not table["slot"].any()
    assert tf.working_set().size == occupied
    # every occurrence counts in show and click, the repeats too
    tf._pool = []
    show, click, touched = tf.expected_counts(np.zeros(0), table)
    occ = np.bincount(rows.ravel(), minlength=occupied)
    assert occ.max() > 1 and occ.sum() == rows.size
    assert (show - table["show"] == occ).all()
    want = np.bincount(rows.ravel(), minlength=occupied,
                       weights=np.repeat(tf.check.labels, S))
    assert (click - table["click"] == want).all()
    assert (touched == np.flatnonzero(occ)).all()
    # the default is a table a slot: disjoint, so no key twice in a line
    per_slot = traffic.Traffic(dict(cfg, slot_tables="per_slot",
                                    occupied_rows=20_000), spec["mix"], 3, 8)
    assert per_slot.sizes.size == S
    assert all(np.unique(r).size == S for r in per_slot.check.rows)
    with pytest.raises(ValueError, match="slot_tables"):
        traffic.Traffic(dict(cfg, slot_tables="one"), spec["mix"], 3, 8)


def test_follow_pads_to_the_vocabulary_and_returns_the_same(probe,
                                                            monkeypatch):
    import jax
    from harness import reference, traffic
    spec, cfg = probe
    steps = 8
    tf = traffic.Traffic(cfg, spec["mix"], 11, steps)
    p0 = jax.device_get(reference.init_params(
        spec["cfg_mod"].param_init(cfg), 11))
    ex, table = tf.check, tf.table()
    assert ex.rows.size > cfg["occupied_rows"]
    padded = []
    step = reference._step

    def recorded(fwd, mm, num_rows, *a, **kw):
        padded.append(num_rows)
        return step(fwd, mm, num_rows, *a, **kw)
    monkeypatch.setattr(reference, "_step", recorded)

    def follow(cfg):
        return reference.follow(cfg, spec["cfg_mod"], p0, ex.rows, ex.labels,
                                ex.dense, steps, table)
    small = follow(cfg)
    large = follow(dict(cfg, occupied_rows=10 ** 9))
    assert padded == [cfg["occupied_rows"]] * steps + [ex.rows.size] * steps
    _assert_same(small, large)
    assert small["x_delta"].any() and small["w_delta"].any()
    assert small["show"].shape == (np.unique(ex.rows).size,)
    # a key held twice counts twice
    occ = np.bincount(ex.rows.ravel(), minlength=cfg["occupied_rows"])
    at = reference.check_rows(ex.rows)
    assert (small["show"] - table["show"][at] == occ[at]).all()


def test_the_probe_rehearses_to_correct_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", PROBE,
         "--seed", str(2 ** 31 + 29), "--seconds", "2", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-2000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, got.stderr[-2000:]
    assert line["compared"]["count_mismatch"] == {"value": 0, "limit": 0}
    assert line["compared"]["compiles_in_window"]["value"] == 0
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["info"]["rehearse"] is True


def test_the_probe_has_what_the_metric_files_name(probe):
    import inspect
    spec, _ = probe
    cfg, mod = spec["cfg"], spec["cfg_mod"]
    assert len(spec["layer"]) == 19 and spec["cell"]["chips"] == 1
    for name, m in spec["layer"].items():
        if m["kind"] == "work_over_peak":
            fn = getattr(mod, m["args"]["work"])
            n = len(inspect.signature(fn).parameters)
            assert fn(cfg, *([100.0] * (n - 1))) > 0, name
    shapes = [s for s, _std in mod.param_init(cfg).values()]
    dense = sum(int(np.prod(s)) for s in shapes)
    assert dense == 2 * 2051 * cfg["tower_hidden"] * 5
    positions = cfg["batch_size"] * cfg["num_sparse_slots"]
    assert positions == 16384 and cfg["row_f32"] == cfg["embedx_dim"] + 9
    assert mod.flops_per_example(cfg) * cfg["batch_size"] == (
        6.0 * positions * dense)
    assert cfg["occupied_rows"] < cfg["pass_capacity"]
