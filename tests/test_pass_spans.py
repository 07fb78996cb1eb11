"""The pass explains itself (ISSUE 25): live nested phase spans on the pass
loop, a pass id on every span of a pass across threads, compiles as spans,
named scopes in the step with the map that makes them readable."""

import contextlib
import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.config import flags
from paddlebox_tpu.config.configs import (SparseOptimizerConfig, TableConfig,
                                          TrainerConfig)
from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
from paddlebox_tpu.embedding import pass_table
from paddlebox_tpu.embedding.pass_table import PassTable, _delta_promote
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.models.base import ModelSpec
from paddlebox_tpu.obs import device as obs_device
from paddlebox_tpu.obs import tracer as obs_tracer
from paddlebox_tpu.obs.tracer import (get_tracer, next_trace_id,
                                      pass_trace_id, step_trace_id)
from paddlebox_tpu.train.preload import run_preloaded_passes
from paddlebox_tpu.train.trainer import BoxTrainer
from paddlebox_tpu.utils.stats import stat_get

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D, NUM_SLOTS = 4, 4

# ISSUE 25, part A: parent -> its child spans, in order. ISSUE 33: the
# feed pass is planned on the feed-ahead thread, under ingest_feed_ahead;
# ingest_feed_pass, on the main thread, installs the plan
CHILDREN = {
    "ingest_feed_ahead": ["ingest_load_join", "feed_fold_join",
                          "feed_unique", "promote_diff", "feed_route_index"],
    "pass_end": ["writeback_select", "writeback_d2h", "writeback_decode",
                 "writeback_store", "pass_mem_check"],
    "train_pass": ["pass_begin", "pass_split_batches", "pass_end",
                   "pass_report"],
}
FULL_BUILD = ["build_store_read", "build_encode", "build_h2d"]
# ISSUE 26: the resident diff assigns the rows the index is built from, so
# promote_diff runs in the feed pass, and only over a resident slab
FEED_INCREMENTAL = ["promote_diff"]
INCREMENTAL = ["promote_store_read", "promote_stage", "promote_dispatch"]
ROOTS = ["ingest_wait_preload", "ingest_feed_pass", "train_pass",
         "pass_release"]
STEP_SCOPES = {"pull", "pool", "fwd_bwd", "dense_opt", "push_grads",
               "push_merge", "push_opt", "push_write"}


def table_cfg():
    return TableConfig(
        embedx_dim=D, pass_capacity=1 << 13,
        optimizer=SparseOptimizerConfig(mf_create_thresholds=0.0,
                                        mf_initial_range=1e-3,
                                        feature_learning_rate=0.1,
                                        mf_learning_rate=0.1))


@contextlib.contextmanager
def fresh_compiles():
    """jax's persistent cache keys a program WITHOUT its metadata, so a
    cache filled before a scope existed hands back an executable whose
    op_names lack it: the scope maps below are read from fresh compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def two_passes(files, feed, scan_chunk=8):
    """A fresh trainer through two preloaded passes; (losses, ring spans).
    400 examples of 32 a batch: one scan chunk of 8, then 5 single steps."""
    get_tracer().clear()
    trainer = BoxTrainer(
        CtrDnn(ModelSpec(num_slots=NUM_SLOTS, slot_dim=3 + D), hidden=(16,)),
        table_cfg(), feed,
        TrainerConfig(dense_lr=0.01, scan_chunk=scan_chunk), seed=0)
    datasets = []
    for _ in range(2):
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        datasets.append(ds)
    try:
        stats = run_preloaded_passes(trainer, datasets)
    finally:
        trainer.close()
    return [s["loss"] for s in stats], get_tracer().all_spans()


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    files, feed = write_synthetic_ctr_files(
        str(tmp_path_factory.mktemp("pass_spans")), num_files=2,
        lines_per_file=200, num_slots=NUM_SLOTS, vocab_per_slot=80,
        max_len=3, seed=13)
    return files, type(feed)(slots=feed.slots, batch_size=32)


@pytest.fixture(scope="module")
def run(data):
    flags.set_flag("dataset_disable_shuffle", True)
    packed0 = stat_get("ingest_batches_packed_lazy")
    try:
        with fresh_compiles():
            losses, spans = two_passes(*data)
        snap = obs_device.snapshot()
    finally:
        flags.set_flag("dataset_disable_shuffle", False)
    return {"losses": losses, "spans": spans, "snapshot": snap,
            "main": threading.get_ident(),
            "packed": stat_get("ingest_batches_packed_lazy") - packed0}


def by_pass(run, k):
    return [s for s in run["spans"] if s[5] == pass_trace_id(0, k)]


def test_both_passes_record_every_phase_span(run):
    first = {s[0] for s in by_pass(run, 0)}
    second = {s[0] for s in by_pass(run, 1)}
    shared = (set(ROOTS) | {c for cs in CHILDREN.values() for c in cs}
              ) - set(FEED_INCREMENTAL)
    assert shared <= first and shared <= second, (
        shared - first, shared - second)
    # pass 0 builds the slab whole; pass 1 finds it resident and promotes
    # the delta, after the prefetcher read pass 1's rows under pass 0
    promote = set(INCREMENTAL) | set(FEED_INCREMENTAL)
    assert set(FULL_BUILD) <= first and not promote & first
    assert promote <= second and not set(FULL_BUILD) & second
    assert "promote_prefetch_finish" in second
    assert {"ingest_parse", "ingest_pack", "host_stage", "scan_dispatch",
            "chunk_drain"} <= first & second


def test_each_child_lies_inside_its_parent_on_the_same_thread(run):
    parents = dict(CHILDREN, pass_begin=FULL_BUILD + INCREMENTAL)
    spans = run["spans"]
    for parent, children in parents.items():
        held = [s for s in spans if s[0] == parent]
        kids = [s for s in spans if s[0] in children]
        assert held and kids, parent
        for name, tid, _tn, t0, t1, _tr in kids:
            assert any(p[1] == tid and p[3] <= t0 and t1 <= p[4]
                       for p in held), (name, parent)
        for p in held:   # children in the table's order, none overlapping
            inside = [s for s in kids if p[3] <= s[3] and s[4] <= p[4]]
            assert [s[0] for s in inside] == [
                c for c in children if c in {s[0] for s in inside}]
            assert all(a[4] <= b[3] for a, b in zip(inside, inside[1:]))
    for name in ROOTS:   # roots are roots: on the main thread, in no span
        for s in (s for s in spans if s[0] == name):
            assert s[1] == run["main"]
            assert not any(o[1] == s[1] and o[3] <= s[3] and s[4] <= o[4]
                           and o is not s for o in spans), name


def test_spans_carry_their_pass_across_threads(run):
    main = run["main"]
    for k in (0, 1):
        mine = by_pass(run, k)
        # the readers parse pass k's two files under pass k's id, though
        # pass 1's ran while pass 0 trained; the stager stages under it
        assert sum(s[0] == "ingest_parse" for s in mine) == 2
        assert all(s[1] != main for s in mine
                   if s[0] in ("ingest_parse", "ingest_merge"))
        assert any(s[0] == "host_stage" and s[1] != main for s in mine)
    t_train0 = [s for s in by_pass(run, 0) if s[0] == "train_pass"][0]
    parse1 = [s for s in by_pass(run, 1) if s[0] == "ingest_parse"]
    assert all(s[4] <= t_train0[4] for s in parse1), "parsed under pass 0"
    named = set(ROOTS) | set(FULL_BUILD) | set(INCREMENTAL) | {
        c for cs in CHILDREN.values() for c in cs} | {
        "ingest_parse", "promote_prefetch_finish", "scan_dispatch",
        "chunk_drain"}
    ids = {pass_trace_id(0, 0), pass_trace_id(0, 1)}
    assert all(s[5] in ids for s in run["spans"] if s[0] in named)


def test_the_feed_pass_of_pass_1_is_planned_under_pass_0(run):
    """ISSUE 33: the feed-ahead thread joins pass 1's load and plans its
    feed pass, under pass 1's id, after pass 0 was installed and before
    pass 0's train_pass ends; the boundary's ingest_feed_pass installs and
    derives nothing."""
    main = run["main"]
    installed0 = [s for s in by_pass(run, 0) if s[0] == "ingest_feed_pass"]
    train0 = [s for s in by_pass(run, 0) if s[0] == "train_pass"]
    assert len(installed0) == len(train0) == 1
    ahead = [s for s in run["spans"] if s[0] == "ingest_feed_ahead"]
    assert [s[5] for s in ahead] == [pass_trace_id(0, 0), pass_trace_id(0, 1)]
    assert all(s[1] != main for s in ahead)
    a1 = ahead[1]
    assert installed0[0][4] <= a1[3] and a1[4] <= train0[0][4]
    planned = [s for s in by_pass(run, 1)
               if s[0] in CHILDREN["ingest_feed_ahead"]]
    assert [s[0] for s in planned] == CHILDREN["ingest_feed_ahead"]
    assert all(s[1] == a1[1] and a1[3] <= s[3] and s[4] <= a1[4]
               for s in planned)
    derived = {"feed_unique", "promote_diff", "feed_route_index"}
    for f in (s for s in run["spans"] if s[0] == "ingest_feed_pass"):
        assert f[1] == main
        assert not [s for s in run["spans"] if s[0] in derived
                    and s[1] == main and f[3] <= s[3] and s[4] <= f[4]]
    assert not [s for s in run["spans"] if s[0] in derived and s[1] == main]


def test_pass_1_s_chunks_are_folded_on_a_thread_of_their_own(run):
    """ISSUE 40: pass 1 succeeds pass 0's map, so each registered key
    chunk (one a parsed file here) is folded against it on the feed-fold
    thread under pass 1's id, before the feed-ahead thread, having joined
    the load and then the fold, finishes the plan; pass 0 has no base and
    folds nothing."""
    folds = [s for s in run["spans"] if s[0] == "feed_fold"]
    assert [s[5] for s in folds] == [pass_trace_id(0, 1)] * 2
    assert {s[2] for s in folds} == {"feed-fold"}
    ahead, = [s for s in by_pass(run, 1) if s[0] == "ingest_feed_ahead"]
    joined, = [s for s in by_pass(run, 1) if s[0] == "feed_fold_join"]
    unique, = [s for s in by_pass(run, 1) if s[0] == "feed_unique"]
    assert ahead[2] == "feed-ahead" and ahead[1] not in {s[1] for s in folds}
    assert all(s[4] <= joined[4] <= unique[3] for s in folds)
    train0, = [s for s in by_pass(run, 0) if s[0] == "train_pass"]
    assert all(s[4] <= train0[4] for s in folds), "folded under pass 0"


def test_a_pass_s_batches_are_packed_at_the_pull_under_its_steps(run):
    """ISSUE 35: pass_split_batches, in train_pass on the main thread,
    takes the plan of the split; the chunk's eight batches are packed by
    a pull, ingest_pack, under the pass's id and before (not inside) its
    host_stage; the five tail batches by the step loop, a step an id.
    That chunk is the pass's first, so the pull and the stage run inside
    stage_ahead on the feed-ahead thread, once the pass's plan is done
    and before the main thread takes the chunk."""
    main = run["main"]
    assert run["packed"] == 2 * 13
    for k in (0, 1):
        mine = by_pass(run, k)
        train, = [s for s in mine if s[0] == "train_pass"]
        split, = [s for s in mine if s[0] == "pass_split_batches"]
        assert split[1] == main
        assert train[3] <= split[3] and split[4] <= train[4]
        ahead, = [s for s in mine if s[0] == "stage_ahead"]
        plan, = [s for s in mine if s[0] == "ingest_feed_ahead"]
        assert ahead[2] == "feed-ahead" and ahead[1] == plan[1] != main
        assert plan[4] <= ahead[3]
        pack, = [s for s in mine if s[0] == "ingest_pack"]
        assert pack[1] == ahead[1]
        stage, = [s for s in mine
                  if s[0] == "host_stage" and s[1] == pack[1]]
        assert ahead[3] <= pack[3] and pack[4] <= stage[3]
        assert stage[4] <= ahead[4]
        taken, = [s for s in mine if s[0] == "chunk_stage_wait"]
        assert stage[4] <= taken[4] <= train[4]
    tail = [s for s in run["spans"]
            if s[0] == "ingest_pack" and s[1] == main]
    staged = [s for s in run["spans"]
              if s[0] == "host_stage" and s[1] == main]
    assert [s[5] for s in tail] == [s[5] for s in staged]
    assert all(p[4] <= h[3] for p, h in zip(tail, staged))
    assert {s[5] for s in tail} == {step_trace_id(0, n)
                                    for n in (9, 10, 11, 12, 13,
                                              22, 23, 24, 25, 26)}


def test_the_stager_s_queue_has_a_span_on_each_edge(chunks_of_four):
    """ISSUE 39: the main thread's q.get() is chunk_stage_wait, a sibling
    of scan_dispatch and chunk_drain inside train_pass; the stager's put
    is stage_queue_full, after the chunk's host_stage; one each a chunk,
    both under the pass's id. The first chunk was staged ahead and is
    taken under a chunk_stage_wait of its own, so the queue holds the
    second and third of the three chunks of four."""
    _losses, spans = chunks_of_four[1]
    main = threading.get_ident()
    for k in (0, 1):
        mine = [s for s in spans if s[5] == pass_trace_id(0, k)]
        train, = [s for s in mine if s[0] == "train_pass"]
        first, *waits = [s for s in mine if s[0] == "chunk_stage_wait"]
        fulls = [s for s in mine if s[0] == "stage_queue_full"]
        stages = [s for s in mine
                  if s[0] == "host_stage" and s[2] == "chunk-stager"]
        dispatches = [s for s in mine if s[0] == "scan_dispatch"]
        assert len(waits) == len(fulls) == len(stages) == 2
        assert len(dispatches) == 3
        assert first[1] == main and first[4] <= dispatches[0][3]
        for wait, full, stage, dispatch in zip(waits, fulls, stages,
                                               dispatches[1:]):
            assert wait[1] == main
            assert train[3] <= wait[3] and wait[4] <= train[4]
            assert wait[4] <= dispatch[3]
            assert full[2] == "chunk-stager" and full[1] == stage[1] != main
            assert stage[4] <= full[3] and full[4] <= train[4]
        # the queue's first chunk: the stager began it as the main thread
        # took the staged-ahead one, so its wait ends after its stage
        assert waits[0][4] >= stages[0][4]
    edges = [s for s in spans
             if s[0] in ("chunk_stage_wait", "stage_queue_full")]
    assert len(edges) == 10


@pytest.fixture(scope="module")
def chunks_of_four(data):
    """The same two passes as three scan chunks of 4 and one single step,
    at prefetch depth 0, 1 and 2: {depth: (losses, spans)}."""
    flags.set_flag("dataset_disable_shuffle", True)
    was = flags.get_flag("chunk_prefetch_depth")
    out = {}
    try:
        for depth in (0, 1, 2):
            flags.set_flag("chunk_prefetch_depth", depth)
            out[depth] = two_passes(*data, scan_chunk=4)
    finally:
        flags.set_flag("chunk_prefetch_depth", was)
        flags.set_flag("dataset_disable_shuffle", False)
    return out


@pytest.mark.parametrize("depth", [0, 1, 2])
def test_each_chunk_has_both_edges_and_no_stager_has_neither(
        chunks_of_four, depth):
    """One chunk_stage_wait on the main thread and one stage_queue_full
    on the stager a chunk the queue carries; with chunk_prefetch_depth 0
    there is no queue and neither span, but for the wait that takes the
    first chunk, staged ahead at every depth; the losses are the same at
    every depth."""
    losses, spans = chunks_of_four[depth]
    assert losses == chunks_of_four[0][0]
    main = threading.get_ident()
    for k in (0, 1):
        mine = [s for s in spans if s[5] == pass_trace_id(0, k)]
        train, = [s for s in mine if s[0] == "train_pass"]
        waits = [s for s in mine if s[0] == "chunk_stage_wait"]
        fulls = [s for s in mine if s[0] == "stage_queue_full"]
        assert len(waits) == len(fulls) + 1 == (3 if depth else 1)
        assert sum(s[0] == "scan_dispatch" for s in mine) == 3
        assert all(s[1] == main for s in waits)
        assert all(s[2] == "chunk-stager" and s[1] != main for s in fulls)
        assert all(train[3] <= s[3] and s[4] <= train[4]
                   for s in waits + fulls)
        # siblings: a wait overlaps no dispatch and no drain
        others = [s for s in mine if s[0] in ("scan_dispatch", "chunk_drain")]
        assert all(w[4] <= o[3] or o[4] <= w[3]
                   for w in waits for o in others)
    named = [s for s in spans
             if s[0] in ("chunk_stage_wait", "stage_queue_full")]
    assert all(s[5] in (pass_trace_id(0, 0), pass_trace_id(0, 1))
               for s in named)


def test_a_step_id_does_not_outlive_the_step_loop(run):
    """The five single steps after the scan chunk set step ids; pass_end,
    after them, is back under the pass's id."""
    stepped = [s for s in run["spans"]
               if s[0] == "host_stage" and s[1] == run["main"]]
    assert len(stepped) == 10
    assert {s[5] for s in stepped} == {step_trace_id(0, n)
                                       for n in (9, 10, 11, 12, 13,
                                                 22, 23, 24, 25, 26)}
    ends = [s for s in run["spans"] if s[0] == "pass_end"]
    assert [s[5] for s in ends] == [pass_trace_id(0, 0), pass_trace_id(0, 1)]


def test_a_chunked_write_back_s_spans_lie_inside_pass_end(data, monkeypatch):
    """ISSUE 38: the write-back crosses a chunk at a time. With the chunk
    cut to 16 rows, every chunk's writeback_d2h, writeback_decode and
    writeback_store are children of pass_end on the main thread under the
    pass's id, in that order; pass_writeback_chunks counts ceil(m / R)."""
    R = 16
    monkeypatch.setattr(pass_table, "_WRITEBACK_CHUNK_BYTES",
                        R * PassTable(table_cfg()).layout.device_bytes_per_row)
    written = []
    plain = PassTable._write_back

    def counting(self, keys, idx):
        assert self._writeback_rows == R
        written.append(idx.size)
        plain(self, keys, idx)

    monkeypatch.setattr(PassTable, "_write_back", counting)
    chunks0 = stat_get("pass_writeback_chunks")
    _, spans = two_passes(*data)
    want = [-(-m // R) for m in written]
    assert len(want) == 2 and min(want) > 1, written
    assert stat_get("pass_writeback_chunks") - chunks0 == sum(want)
    per_chunk = ["writeback_d2h", "writeback_decode", "writeback_store"]
    main = threading.get_ident()
    for k, n_chunks in enumerate(want):
        mine = [s for s in spans if s[5] == pass_trace_id(0, k)]
        (end,) = [s for s in mine if s[0] == "pass_end"]
        kids = sorted((s for s in spans if s[0] in per_chunk),
                      key=lambda s: s[3])
        kids = [s for s in kids if end[3] <= s[3] and s[4] <= end[4]]
        assert [s[0] for s in kids] == per_chunk * n_chunks
        assert all(s[1] == main == end[1] and s[5] == end[5] for s in kids)
        assert all(a[4] <= b[3] for a, b in zip(kids, kids[1:]))
    assert sum(s[0] in per_chunk for s in spans) == 3 * sum(want)


def test_trace_ids_of_different_kinds_never_collide():
    pid = pass_trace_id(3, 7)
    assert pid >> 61 == 1 and (pid >> 48) & 0x1FFF == 3 and pid & 0xFFFF == 7
    assert pass_trace_id(3, 7) != pass_trace_id(3, 8) != pass_trace_id(4, 8)
    assert step_trace_id(3, 7) >> 61 == 0
    assert next_trace_id() >> 63 == 1
    assert ((1 << 62) | step_trace_id(3, 7)) >> 61 == 2   # a mesh frame's


def test_pass_begin_and_pass_end_open_a_trace_annotation():
    opened = []

    class Stub:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            opened.append("/" + self.name)

    table = PassTable(table_cfg(), seed=0)
    table.begin_feed_pass()
    table.add_keys(np.arange(1, 50, dtype=np.uint64))
    table.end_feed_pass()
    obs_tracer.set_jax_annotation(Stub)
    try:
        table.begin_pass()
        table.note_touched(np.arange(10))
        table.end_pass()
    finally:
        obs_tracer.set_jax_annotation(None)
    assert opened[0] == "pass_begin" and opened[-1] == "/pass_end"
    i, j = opened.index("/pass_begin"), opened.index("pass_end")
    assert i < j
    assert opened[1:i] == [x for c in FULL_BUILD for x in (c, "/" + c)]
    assert "writeback_d2h" in opened[j:]


def test_a_fresh_jit_records_one_backend_compile_and_no_entry(run):
    x = jnp.arange(7.0)

    def count():
        return sum(s[0] == "backend_compile"
                   for s in get_tracer().all_spans())

    def compiles():
        return {n: e["compiles"]
                for n, e in obs_device.snapshot()["entries"].items()}

    spans0, stat0, entries0 = (count(), stat_get("device_backend_compiles"),
                               compiles())
    y = jax.jit(lambda v: v * 3.0 + 1.0)(x)  # boxlint: disable=BX901
    assert count() == spans0 + 1
    assert stat_get("device_backend_compiles") == stat0 + 1
    assert compiles() == entries0
    assert np.asarray(y)[1] == 4.0
    last = [s for s in get_tracer().all_spans()
            if s[0] == "backend_compile"][-1]
    assert 0.0 < last[4] - last[3] < 60.0


def test_an_instrumented_compile_records_a_device_compile_span(run):
    compiled = [s for s in run["spans"] if s[0] == "device_compile"]
    inner = [s for s in run["spans"] if s[0] == "backend_compile"]
    assert compiled and inner
    # scan_steps and train_step compile inside pass 0's train_pass; each
    # device_compile holds the backend compile it caused
    for s in compiled:
        assert any(s[3] <= b[3] and b[4] <= s[4] and b[1] == s[1]
                   for b in inner)


@pytest.mark.parametrize("entry", ["scan_steps", "train_step"])
def test_the_scope_map_names_every_phase_of_the_step(run, entry):
    e = run["snapshot"]["entries"][entry]
    assert e["module"].startswith("jit_")
    assert STEP_SCOPES <= set(e["scopes"].values()), (
        STEP_SCOPES - set(e["scopes"].values()))
    assert "" in e["scopes"].values()       # parameters, the loop, tuples
    assert not any(k.startswith("%") for k in e["scopes"])


def test_the_scope_map_names_delta_promote_s_phases():
    cap, width = 64, 8
    with fresh_compiles():
        text = _delta_promote.lower(
            jnp.zeros((cap, width)), jnp.zeros(4, jnp.int32),
            jnp.zeros((4, width))).compile().as_text()
    scopes = set(obs_device.scope_map(text).values())
    assert "promote_scatter" in scopes and "promote_permute" not in scopes


def test_the_scope_map_names_the_write_back_s_gather():
    with fresh_compiles():
        text = pass_table._writeback_gather.lower(
            jnp.zeros((64, 8)), jnp.zeros(4, jnp.int32)).compile().as_text()
    assert "writeback_gather" in set(obs_device.scope_map(text).values())


def test_scope_map_reads_the_innermost_scope_through_autodiff_wrappers():
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "%fused_computation (p: f32[4]) -> f32[4] {",
        '  %p = f32[4]{0} parameter(0)',
        '  ROOT %add.1 = f32[4]{0} add(%p, %p), metadata={op_name='
        '"jit(f)/jit(main)/while/body/closed_call/fwd_bwd/'
        'transpose(jvp(pool))/add" source_file="x.py"}',
        "}",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        '  %a = f32[4]{0} parameter(0), metadata={op_name="a"}',
        '  %fusion.2 = f32[4]{0} fusion(%a), kind=kLoop, '
        'calls=%fused_computation, metadata={op_name='
        '"jit(f)/jit(main)/push_write/scatter-add"}',
        '  ROOT %copy.3 = f32[4]{0} copy(%fusion.2)',
        "}"])
    assert obs_device.scope_map(text) == {
        "p": "", "add.1": "pool", "a": "", "fusion.2": "push_write",
        "copy.3": ""}


def test_with_obs_trace_off_no_span_and_the_same_losses(run, data):
    flags.set_flag("obs_trace", False)
    flags.set_flag("dataset_disable_shuffle", True)
    clock = ("feed_plan_us", "feed_plan_slack_us", "feed_plan_fold_us",
             "feed_keys_folded", "feed_plan_arrived_keys",
             "feed_plan_departed_keys", "feed_index_shared",
             "feed_index_rebuilt")
    plan_us = [stat_get(c) for c in clock]
    try:
        losses, spans = two_passes(*data)
    finally:
        flags.set_flag("obs_trace", True)
        flags.set_flag("dataset_disable_shuffle", False)
        obs_tracer.configure_from_flags()
    assert spans == []
    assert losses == run["losses"]
    # ISSUE 39: the plan's clock is its spans' stamps; no span, no time.
    # ISSUE 40: nor any of the counts the plan carries beside them
    assert plan_us == [stat_get(c) for c in clock]
    assert plan_us[2] > 0 and plan_us[3] > 0    # the traced run added them


def test_profiler_trace_marks_the_traced_stretch(monkeypatch, tmp_path):
    from paddlebox_tpu.utils import profiler
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    get_tracer().clear()
    with profiler.trace(str(tmp_path)):
        with obs_tracer.span("inside"):
            assert obs_tracer._JAX_ANNOTATE is jax.profiler.TraceAnnotation
    assert obs_tracer._JAX_ANNOTATE is None and calls == ["start", "stop"]
    spans = {s[0]: s for s in get_tracer().all_spans()}
    outer, inner = spans["profiler_trace"], spans["inside"]
    assert outer[3] <= inner[3] and inner[4] <= outer[4]


def test_scope_times_sums_to_the_ops_total():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import scope_times
    finally:
        sys.path.pop(0)
    tr = scope_times.tr
    trace = tr.load(os.path.join(ROOT, "benchmarks", "testdata",
                                 "small.xplane.pb"))
    snapshot = {"entries": {
        "scan_steps": {"module": "jit_scan_steps", "scopes": {
            "fusion.8": "fwd_bwd", "copy.11": "", "copy-start.1": "pull",
            "copy-done.1": "pull", "while": "pool"}},
        "no_map": {"compiles": 1}}}
    snapshot = json.loads(json.dumps(snapshot))    # as a dumped one reads
    got = scope_times.scope_times(trace, snapshot)
    assert set(got) == {"jit_scan_steps"}
    ops = [(tr.short_name(n), e - s) for n, s, e
           in trace["devices"]["/device:TPU:0"][tr.OPS_LINE]]
    want = sum(d for (_op, code), d in ops if code not in tr.CONTAINERS)
    acc = got["jit_scan_steps"]
    assert sum(acc.values()) == pytest.approx(want, rel=1e-9)
    assert set(acc) == {"fwd_bwd", "pull", scope_times.NO_SCOPE,
                        scope_times.NOT_IN_MAP}     # %copy-start, -done
    assert acc["fwd_bwd"] == pytest.approx(
        sum(d for (op, _c), d in ops if op == "%fusion.8"), rel=1e-9)
    assert scope_times.main(["scope_times.py"]) == 2


# ISSUE 39: the eight metric files over the plan's counters and the
# stager's two spans, and what each reads from a slice of 2 passes, 16 steps
FEED_PLAN_METRICS = {
    "pass_lifecycle.feed_plan_us_per_pass": 1_900_000.0,
    "pass_lifecycle.feed_plan_load_join_us_per_pass": 60_000.0,
    "pass_lifecycle.feed_plan_unique_us_per_pass": 680_000.0,
    "pass_lifecycle.feed_plan_diff_us_per_pass": 390_000.0,
    "pass_lifecycle.feed_plan_index_us_per_pass": 750_000.0,
    "pass_lifecycle.feed_plan_slack_us_per_pass": 0.0,
    "dispatch.stage_wait_ms_per_step": 12.5,
    "host_stage.queue_full_ms_per_step": 3.0,
    # ISSUE 40: the fold, the index shared, the delta's size
    "pass_lifecycle.feed_plan_fold_us_per_pass": 450_000.0,
    "pass_lifecycle.feed_index_shared_per_pass": 1.0,
    "pass_lifecycle.feed_plan_delta_keys_per_pass": 1_250_000.0,
}


@pytest.mark.parametrize("name", sorted(FEED_PLAN_METRICS))
def test_the_metric_files_over_the_plan_s_clock_reduce(name):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        from harness import reducers
    finally:
        sys.path.pop(0)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert spec["kind"] in reducers.KINDS and "workloads" not in entry
    assert all(spec[k] == entry[k]
               for k in ("layer", "unit", "moves", "better"))
    counter = spec["kind"] == "counter_delta_per"
    assert entry["source"] == ("program_counter" if counter
                               else "program_span")
    assert spec["what"].endswith(
        "a program without the counter reads 0") == counter
    ctx = {"passes": 2, "steps": 16, "examples": 16 * 32,
           "counters": {"feed_plan_us": 3_800_000,
                        "feed_plan_load_join_us": 120_000,
                        "feed_plan_unique_us": 1_360_000,
                        "feed_plan_diff_us": 780_000,
                        "feed_plan_index_us": 1_500_000,
                        "feed_plan_slack_us": 0,
                        "feed_plan_fold_us": 900_000, "feed_index_shared": 2,
                        "feed_plan_arrived_keys": 1_300_000,
                        "feed_plan_departed_keys": 1_200_000},
           "spans": [("chunk_stage_wait", 1.0, 1.1),
                     ("chunk_stage_wait", 2.0, 2.1),
                     ("stage_queue_full", 1.2, 1.224),
                     ("stage_queue_full", 2.2, 2.224),
                     ("host_stage", 1.0, 1.2)]}
    assert reducers.reduce_metric(spec, ctx) == pytest.approx(
        FEED_PLAN_METRICS[name], rel=1e-9)
    # the parent's side of a pair: the counters read 0 (stat_get of a name
    # nobody added), the spans are not there and the metric is left out
    empty = dict(ctx, spans=[], counters={c: 0 for c in ctx["counters"]})
    assert reducers.reduce_metric(spec, empty) == (0.0 if counter else None)
