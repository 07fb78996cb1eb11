"""Pod-sharded pass table: the multi-chip BoxPS/HeterComm engine on ICI.

Re-design of HeterComm (paddle/fluid/framework/fleet/heter_ps/heter_comm_inl.h)
for the TPU: the reference shards its hash table by ``key % num_devices``
(split_input_to_shard, inl:1117) and moves key/value traffic over explicit
p2p copies (walk_to_dest/walk_to_src, inl:273,1296-1445). Here:

  * each mesh device owns one dense per-pass shard slab [shard_cap, width]
    (the feed pass gives the exact key set per shard — same dense-slab
    trick as the single-chip PassTable);
  * the host packer pre-buckets each batch's keys by destination shard into
    fixed [num_shards, bucket_cap] local-id buckets + a restore index
    (the DedupKeysAndFillIdx analog, host-side);
  * pull = all_to_all(id buckets) → local gather → all_to_all(values) →
    restore; push = scatter-merge grads into buckets → all_to_all →
    local dedup + in-table optimizer. The two all_to_alls ARE
    walk_to_dest/walk_to_src, riding ICI as XLA collectives.

Everything device-side is static-shaped and lives inside ONE shard_map'd
train step (parallel/sharded_trainer.py), so XLA overlaps the a2a with the
dense compute where profitable.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.config.configs import TableConfig
from paddlebox_tpu.embedding.accessor import (PushLayout, ValueLayout,
                                              decode_slab_rows_np,
                                              encode_slab_rows_np)
from paddlebox_tpu.embedding.native_store import make_host_store
from paddlebox_tpu.obs import beat as obs_beat
from paddlebox_tpu.obs.tracer import record_span
from paddlebox_tpu.utils.stats import hist_observe, stat_add
from paddlebox_tpu.utils.lockwatch import make_lock


_warned_numpy_route = False


def _route_lib():
    """Native router (route.cc) or None → vectorized numpy fallback.
    The fallback is LOUD (warn once + stat): numpy manages ~1M keys/s vs
    the native router's ~13M, which at pass scale is a real regression."""
    from paddlebox_tpu.native.build import get_lib
    lib = get_lib()
    if lib is not None and hasattr(lib, "rt_bucketize"):
        return lib
    global _warned_numpy_route
    if not _warned_numpy_route:
        _warned_numpy_route = True
        import logging
        logging.getLogger("paddlebox_tpu").warning(
            "sharded route: native router unavailable — numpy bucketize "
            "fallback active (~13x slower key routing)")
        stat_add("route_numpy_fallback")
    return None


@dataclasses.dataclass
class ShardedBatchIndex:
    """Host-built routing for one batch's keys (static shapes).

    buckets:  [P, KB] int32 — per-destination-shard LOCAL ids (dedup'd per
              batch); padding slots hold shard_cap-1 (the trash row)
    restore:  [K] int32 — flattened bucket slot (s*KB + j) for each of the
              batch's K key positions (occurrences of the same key share a
              slot); invalid key positions point at slot 0 and must be
              masked by the batch's `valid`
    overflow: keys dropped because a shard bucket filled up
    """

    buckets: np.ndarray
    restore: np.ndarray
    overflow: int


def exchange_outgoing_buckets(buckets_local: np.ndarray,
                              local_positions: List[int],
                              num_devices: int,
                              all_gather) -> np.ndarray:
    """Cluster-wide per-step bucket exchange (round-5 verdict item 2):
    every process contributes its LOCAL source devices' outgoing id
    buckets and receives the GLOBAL [num_devices(src), P, KB] array in
    mesh-device order — which makes each destination shard's incoming
    a2a ids host-known everywhere, so the scatter-free push (host dedup +
    pos maps) works at jax.process_count() > 1. This is the host-plane
    twin of the device a2a (the reference routes cluster-wide on device:
    dedup_keys_and_fillidx + split_input_to_shard,
    heter_comm_inl.h:2231,1117).

    buckets_local: [n_local, P, KB] int32, in local-position order.
    all_gather: fleet.all_gather (any rank order — each part carries its
    own global positions in a header, so fleet rank need not equal jax
    process index).
    """
    import time as _time
    bl = np.ascontiguousarray(buckets_local, np.int32)
    n_local, P, KB = bl.shape
    t0 = _time.perf_counter()
    header = np.array([n_local, P, KB] + list(local_positions), np.int32)
    payload = np.concatenate([header, bl.ravel()])
    out = np.empty((num_devices, P, KB), np.int32)
    seen = np.zeros(num_devices, bool)
    gathered = all_gather(payload)
    for part in gathered:
        part = np.asarray(part, np.int32)
        nl, p2, kb2 = part[0], part[1], part[2]
        if (p2, kb2) != (P, KB):
            raise ValueError(
                f"bucket-exchange shape mismatch: peer sent P={p2},"
                f"KB={kb2}, local is P={P},KB={KB}")
        pos = part[3:3 + nl]
        bufs = part[3 + nl:].reshape(nl, P, KB)
        out[pos] = bufs
        seen[pos] = True
    if not seen.all():
        raise RuntimeError(
            "bucket exchange incomplete: no contribution for device "
            f"positions {np.nonzero(~seen)[0].tolist()}")
    # wire attribution (weak #6): this rank writes its payload once and
    # reads every rank's back through the central store
    t1 = _time.perf_counter()
    stat_add("hostplane_exchange_bytes",
             int(payload.nbytes) * (1 + len(gathered)))
    stat_add("hostplane_exchange_us", int((t1 - t0) * 1e6))
    stat_add("hostplane_exchange_steps")
    hist_observe("hostplane_exchange_us", (t1 - t0) * 1e6)
    record_span("hostplane_store_exchange", t0, t1)
    # the store funnel is the progress boundary on the hostplane=store
    # plane (the p2p plane beats inside MeshComm.exchange)
    obs_beat("store_exchange")
    return out


def _mesh_dest_plan(mesh, local_positions, num_devices: int, policy=None):
    """Per-peer destination lists for the p2p exchanges. Round 13: the
    plan is POLICY-OWNED (parallel/sharding.py) — the policy decides
    which peers a rank exchanges with; `None` keeps the validated
    owner-map default every shipped policy rides (and the pre-policy
    behavior, bit-for-bit)."""
    from paddlebox_tpu.parallel.sharding import default_dest_plan
    plan = policy.dest_plan if policy is not None else default_dest_plan
    return plan(mesh, local_positions, num_devices)


def exchange_incoming_p2p(buckets_local: np.ndarray,
                          local_positions: List[int],
                          num_devices: int, mesh, policy=None):
    """P2P twin of exchange_outgoing_buckets (the tentpole a2a): rank r
    ships the owner of destination shard d ONLY its buckets[:, d, :]
    column — O(W*P*KB) direct bytes per step instead of every rank's full
    [n_local, P, KB] set bouncing through the central store
    (O(W^2*P*KB) through one NIC). Returns {d: [num_devices, KB] int32}
    incoming-id arrays in global source-device order for this process's
    OWNED destinations — exactly the concatenation stage_push_dedup's
    per-destination dedup consumes, so the staging products stay
    bit-identical to the store path.
    """
    import time as _time
    bl = np.ascontiguousarray(buckets_local, np.int32)
    n_local, P, KB = bl.shape
    dest_of_rank = _mesh_dest_plan(mesh, local_positions, num_devices,
                                   policy)
    t0 = _time.perf_counter()
    parts = {}
    for r, dests in enumerate(dest_of_rank):
        # header: n_local, KB, n_dests, src positions..., dest positions...
        header = np.array([n_local, KB, len(dests)]
                          + list(local_positions) + list(dests), np.int32)
        parts[r] = np.concatenate(
            [header, bl[:, dests, :].ravel()])
    got = mesh.exchange(parts)
    mine = dest_of_rank[mesh.rank]
    out = {d: np.empty((num_devices, KB), np.int32) for d in mine}
    seen = np.zeros(num_devices, bool)
    for part in got.values():
        part = np.asarray(part, np.int32)
        nl, kb2, nd = int(part[0]), int(part[1]), int(part[2])
        if kb2 != KB:
            raise ValueError("p2p bucket exchange KB mismatch: peer sent "
                             "KB=%d, local is KB=%d" % (kb2, KB))
        srcs = part[3:3 + nl]
        dests = part[3 + nl:3 + nl + nd]
        if sorted(dests.tolist()) != sorted(mine):
            raise ValueError(
                "p2p bucket exchange routed to the wrong owner: got "
                "destinations %s, own %s" % (dests.tolist(), mine))
        block = part[3 + nl + nd:].reshape(nl, nd, KB)
        for j, d in enumerate(dests.tolist()):
            out[d][srcs] = block[:, j, :]
        seen[srcs] = True
    if not seen.all():
        raise RuntimeError(
            "p2p bucket exchange incomplete: no contribution for source "
            f"positions {np.nonzero(~seen)[0].tolist()}")
    # like-for-like NIC accounting with the store path (which counts its
    # 1 write + W reads): sends to W-1 peers PLUS receives from W-1 peers
    wire = sum(int(p.nbytes) for r, p in parts.items() if r != mesh.rank) \
        + sum(int(p.nbytes) for r, p in got.items() if r != mesh.rank)
    t1 = _time.perf_counter()
    stat_add("hostplane_exchange_bytes", wire)
    stat_add("hostplane_exchange_us", int((t1 - t0) * 1e6))
    stat_add("hostplane_exchange_steps")
    hist_observe("hostplane_exchange_us", (t1 - t0) * 1e6)
    record_span("hostplane_p2p_exchange", t0, t1)
    return out


def exchange_push_uids_p2p(buckets_local: np.ndarray,
                           local_positions: List[int], num_devices: int,
                           shard_cap: int, mesh, pool=None, policy=None):
    """Dedup BEFORE the network (composes the round-8 uid wire with the
    p2p mesh): for every destination shard this rank sorts-uniques its
    LOCAL contribution and ships the owner only that vector; the owner
    unions the per-source vectors — the same id set, hence bit-identical
    dedup_uids_sorted products, as deduping the full concatenation after
    a raw exchange, at a fraction of the wire bytes (duplicates never
    travel). Returns {d: uids[num_devices*KB] int32} for owned
    destinations, tail-padded exactly like dedup_uids_sorted.

    pool: optional thread pool for the num_devices sender-side np.unique
    calls (the dominant pre-wire cost; the sort releases the GIL) — the
    runners pass their stager pool.

    policy (round 13): a parallel/sharding.ShardingPolicy — owns the
    per-peer dest plan, and when it carries a frozen replicated hot tier
    (2d-grid) the hot local ids are DROPPED from every shipped vector
    and re-added whole by the owner: replicated rows never travel, and
    since the hot set is cluster-agreed at the pass freeze the union
    still covers every id the destination's device a2a will carry. The
    staged vector over-approximates by hot ids that skipped this step —
    their merged gradients are zero, a value-level no-op in the
    in-table optimizer (the replication premise: hot rows are touched
    essentially every step)."""
    import time as _time
    bl = np.ascontiguousarray(buckets_local, np.int32)
    n_local, P, KB = bl.shape
    K = num_devices * KB
    # same contract dedup_uids_sorted enforces on the post-wire path: a
    # negative id would sort FIRST and silently shift every device-side
    # searchsorted mapping instead of failing loud
    if bl.size and int(bl.min()) < 0:
        raise ValueError("exchange_push_uids_p2p expects nonnegative "
                         "int32 pass-local ids")
    dest_of_rank = _mesh_dest_plan(mesh, local_positions, num_devices,
                                   policy)
    hot_of = (policy.hot_local_ids if policy is not None
              else (lambda d: None))
    t0 = _time.perf_counter()
    mapper = pool.map if pool is not None else map

    def uniq_dest(d):
        from paddlebox_tpu.embedding.pass_table import sorted_member
        u = np.unique(bl[:, d, :])
        hot = hot_of(d)
        if hot is not None and hot.size and u.size:
            # replicated ids never travel: both vectors sorted, one
            # membership probe
            u = u[~sorted_member(hot, u)[1]]
        return u

    uniq_of = list(mapper(uniq_dest, range(num_devices)))
    parts = {}
    for r, dests in enumerate(dest_of_rank):
        uniqs = [uniq_of[d] for d in dests]
        lens = [u.size for u in uniqs]
        header = np.array([KB, len(dests)] + list(dests) + lens, np.int32)
        parts[r] = np.concatenate([header] + uniqs)
    got = mesh.exchange(parts)
    mine = dest_of_rank[mesh.rank]
    vecs = {d: [] for d in mine}
    for part in got.values():
        part = np.asarray(part, np.int32)
        kb2, nd = int(part[0]), int(part[1])
        if kb2 != KB:
            raise ValueError("p2p uid exchange KB mismatch: peer sent "
                             "KB=%d, local is KB=%d" % (kb2, KB))
        dests = part[2:2 + nd].tolist()
        lens = part[2 + nd:2 + 2 * nd]
        offs = np.concatenate([[0], np.cumsum(lens)]) + 2 + 2 * nd
        for j, d in enumerate(dests):
            vecs[d].append(part[offs[j]:offs[j + 1]])
    out = {}
    for d in mine:
        hot = hot_of(d)
        if hot is not None and hot.size:
            # the owner re-adds its whole replicated set (sorted int32)
            vecs[d].append(np.asarray(hot, np.int32))
        uniq = np.unique(np.concatenate(vecs[d]))
        uids = np.empty(K, np.int32)
        n = uniq.size
        if n > K:
            raise RuntimeError(
                "p2p uid exchange: union of %d incoming + replicated "
                "ids exceeds the staged vector length %d for dest %d — "
                "sharding_hot_cap/bucket_cap are inconsistent" % (n, K, d))
        uids[:n] = uniq
        uids[n:] = shard_cap + np.arange(K - n, dtype=np.int32)
        out[d] = uids
    # sends + receives, matching the store path's 1-write + W-reads count
    wire = sum(int(p.nbytes) for r, p in parts.items() if r != mesh.rank) \
        + sum(int(p.nbytes) for r, p in got.items() if r != mesh.rank)
    t1 = _time.perf_counter()
    stat_add("hostplane_exchange_bytes", wire)
    stat_add("hostplane_exchange_us", int((t1 - t0) * 1e6))
    stat_add("hostplane_exchange_steps")
    hist_observe("hostplane_exchange_us", (t1 - t0) * 1e6)
    record_span("hostplane_uid_exchange", t0, t1)
    return out


def stage_push_dedup(buckets, local_positions, num_devices: int,
                     shard_cap: int, multiprocess: bool, all_gather,
                     rebuild: bool, pool, note_touched=None,
                     uid_only: bool = False, mesh=None, policy=None):
    """Per-destination push-dedup staging shared by BOTH sharded runners
    (trainer's _step_host_arrays + pipeline's device_batch): makes each
    shard's incoming a2a ids host-known (exchange_outgoing_buckets when
    multi-process), then fans per-destination dedup (+ rebuild pos maps)
    onto the stager pool. Returns {"push_uids": [...], "push_perm": ...,
    "push_inv": ..., ["push_pos": ...]} in destination order (owned
    destinations only in a multi-process job — the process-local piece
    of the [P, ...] global arrays).

    uid_only (h2d_uid_wire, round 8): stage ONLY the per-destination
    SORTED uid vector — the device step already holds each shard's
    incoming ids (the a2a'd buckets) and derives perm/inv (and the
    rebuild pos) by searchsorted against the sorted uids
    (push_sparse_uidwire). Cuts the per-step staged push wire from
    3-4 [P, P*KB]-shaped arrays to one, and the host dedup to one
    np.unique per destination; composes with the multi-process bucket
    exchange unchanged (the uids must still be host-known cluster-wide
    for the touched-row accounting and writeback delta).

    mesh (hostplane=p2p, round 9): a fleet MeshComm — the multi-process
    exchange rides the persistent p2p socket mesh instead of the store
    allgather: raw bucket columns a2a for the full-product wire, or the
    per-destination PRE-DEDUPED sorted uid vectors under uid_only (dedup
    moves before the network). Staging products are bit-identical to the
    store path either way. None = the store allgather (the loud-fallback
    target).

    policy (round 13): the ShardingPolicy that routed these buckets —
    the p2p exchanges ride its dest plan and (2d-grid) its replicated
    hot-key wire filter. None = the key-mod-equivalent default plan
    (bit-identical to the pre-policy path)."""
    from paddlebox_tpu.embedding.pass_table import (dedup_ids,
                                                    dedup_uids_sorted,
                                                    pos_for_rebuild)
    uids_by_dest = inc = global_buckets = None
    if multiprocess:
        dests = local_positions
        if mesh is not None and uid_only:
            uids_by_dest = exchange_push_uids_p2p(
                np.stack(buckets), local_positions, num_devices,
                shard_cap, mesh, pool=pool, policy=policy)
        elif mesh is not None:
            inc = exchange_incoming_p2p(
                np.stack(buckets), local_positions, num_devices, mesh,
                policy=policy)
        else:
            global_buckets = exchange_outgoing_buckets(
                np.stack(buckets), local_positions, num_devices,
                all_gather)
    else:
        global_buckets = buckets
        dests = range(num_devices)
    if inc is not None:
        incoming_of = lambda d: inc[d].reshape(-1)  # noqa: E731
    else:
        incoming_of = lambda d: np.concatenate(  # noqa: E731
            [global_buckets[src][d] for src in range(num_devices)])

    def dedup_dest(d):
        if uids_by_dest is not None:
            uids = uids_by_dest[d]
            perm = inv = None
        elif uid_only:
            uids = dedup_uids_sorted(incoming_of(d), shard_cap)
            perm = inv = None
        else:
            uids, perm, inv, _n_u = dedup_ids(incoming_of(d), shard_cap)
        if note_touched is not None:
            # every id this destination shard will push rides these uids —
            # the per-pass touched-row accumulation point (incremental
            # EndPass writes back only these rows)
            note_touched(d, uids)
        pos = (pos_for_rebuild(uids, shard_cap)
               if rebuild and not uid_only else None)
        return uids, perm, inv, pos

    out = {"push_uids": []}
    if not uid_only:
        out.update(push_perm=[], push_inv=[])
    for uids, perm, inv, pos in pool.map(dedup_dest, dests):
        out["push_uids"].append(uids)
        if perm is not None:
            out["push_perm"].append(perm)
            out["push_inv"].append(inv)
        if pos is not None:
            out.setdefault("push_pos", []).append(pos)
    return out


class ShardedPassTable:
    """Host-side orchestration of P shard slabs with the BoxPS pass cadence.

    Device arrays are produced per pass as a stacked [P, shard_cap, width]
    global array to be sharded over the mesh axis; the device compute lives
    in sharded_trainer's shard_map step.
    """

    def __init__(self, table: TableConfig, num_shards: int,
                 bucket_cap: int, seed: int = 0,
                 owned_shards: Optional[List[int]] = None,
                 store_factory=None, policy=None) -> None:
        """owned_shards: in a multi-process job each process hosts the full
        store only for the shards whose mesh device it owns (the reference's
        per-node PS shard layout); None = own all (single process). Routing
        state (_shard_keys) is always GLOBAL — any batch may reference any
        shard.

        store_factory(layout, table, seed) -> store overrides the default
        local host store — e.g. embedding.ps_store.ps_store_factory puts
        the distributed CPU PS behind every shard (the GPUPS BuildPull/
        EndPass composition, ps_gpu_wrapper.cc:337,983).

        policy (round 13): the parallel/sharding.ShardingPolicy that owns
        key->shard routing (feed-pass assignment, per-batch bucketize,
        promote prefetch, checkpoint views all route through it); None =
        resolve from the sharding_policy flag (default key-mod, bit-
        identical to the pre-policy key % P path)."""
        from paddlebox_tpu.parallel.sharding import resolve_sharding_policy
        self.policy = policy or resolve_sharding_policy(num_shards)
        if self.policy.num_shards != num_shards:
            raise ValueError(
                "sharding policy built for %d shards, table has %d"
                % (self.policy.num_shards, num_shards))
        self.config = table
        from paddlebox_tpu.embedding.pass_table import _slab_embed_dtype
        self.layout = ValueLayout(table.embedx_dim, table.optimizer.optimizer,
                                  expand_dim=table.expand_embed_dim,
                                  embed_dtype=_slab_embed_dtype())
        self.push_layout = PushLayout(table.embedx_dim,
                                      table.expand_embed_dim)
        self.num_shards = num_shards
        self.bucket_cap = bucket_cap
        if table.pass_capacity % num_shards:
            raise ValueError("pass_capacity must divide evenly into shards")
        self.shard_cap = table.pass_capacity // num_shards
        self.owned_shards = (list(owned_shards) if owned_shards is not None
                             else list(range(num_shards)))
        owned = set(self.owned_shards)
        make_store = store_factory or make_host_store
        # the LIST is immutable after this line (ref-grabs and is-None
        # presence probes are lock-free by design); the store OBJECTS'
        # contents move under spill/resize, so any lookup/write_back while
        # a PromotePrefetcher can be live holds store_lock. Lock-free
        # boundary sites carry an explicit boxlint disable + rationale.
        self.stores = [make_store(self.layout, table, seed + s)  # guarded-by: store_lock
                       if s in owned else None
                       for s in range(num_shards)]
        self._feed_keys: List[np.ndarray] = []
        self._shard_keys: Optional[List[np.ndarray]] = None  # sorted unique per shard
        self._in_feed_pass = False
        self._test_mode = False
        self._route_index = None  # native pass index handle
        self._overflow_warned = False  # one warning per pass (reset per feed)
        # incremental pass lifecycle (per-shard host residency cache):
        # _res_keys[s]/_res_rows[s] mirror the rows the store holds for the
        # last built pass, so the next _build_one promotes only the key
        # DELTA (numpy row moves instead of store hash-gathers) and the
        # end-of-pass writeback touches only rows the pass pushed.
        self._res_keys: dict = {}
        self._res_rows: dict = {}
        self._touched_sh: Optional[dict] = None  # shard -> bool[shard_cap]
        self._touch_seen = False  # any mark this pass? (else full writeback)
        self._staged_sh: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self.store_lock = make_lock("ShardedPassTable.store_lock")
        # touched-row journal (round 15): when attached, every end-of-pass
        # write-back also appends its (keys, rows) delta, and the
        # out-of-cadence lifecycle mutations append event records
        self._journal = None

    # ------------------------------------------------------------- journal
    # setup-time wiring, called before any worker thread exists
    def attach_journal(self, journal) -> None:  # boxlint: disable=BX401
        """Attach a train.journal.TouchedRowJournal: end-of-pass write-
        backs append their touched (keys, rows) delta; end_day/shrink
        append their deterministic event records; local-store spill and
        fault-in append MOVE records through each owned store's journal
        sink (installed here). Spill on a store WITHOUT a sink (PS-backed
        shards — server-side tier) and external loads still taint (see
        journal.py for the replay contract)."""
        self._journal = journal
        for st in self.stores:
            set_sink = getattr(st, "set_journal_sink", None)
            if set_sink is not None:
                set_sink(None if journal is None else journal.append_move)

    def _journal_rows(self, keys: np.ndarray, rows: np.ndarray) -> None:
        if self._journal is not None:
            self._journal.append_rows(keys, rows)

    def _journal_event(self, code: int) -> None:
        if self._journal is not None:
            self._journal.append_event(code)

    def _drop_route_index(self) -> None:
        from paddlebox_tpu.native.build import destroy_route_index
        destroy_route_index(self._route_index)
        self._route_index = None

    def __del__(self):
        try:
            self._drop_route_index()
        except Exception:  # rationale: __del__ may run with a
            # half-torn-down interpreter where even logging fails;
            # close() is the loud path, this is the last-resort guard
            pass

    # ------------------------------------------------------- pass lifecycle
    def begin_feed_pass(self) -> None:
        if self._in_feed_pass:
            raise RuntimeError("feed pass already open")
        self._feed_keys = []
        self._in_feed_pass = True

    def add_keys(self, keys: np.ndarray) -> None:
        if not self._in_feed_pass:
            raise RuntimeError("add_keys outside feed pass")
        keys = np.asarray(keys, dtype=np.uint64)
        self._feed_keys.append(keys)
        if self.policy.wants_observe:
            # the 2d-grid hot tier's frequency stream (reader threads;
            # the sketch locks internally). Rank-local counts are summed
            # cluster-wide at end_feed_pass before the hot set freezes.
            self.policy.observe(keys)

    def end_feed_pass(self, allgather=None) -> None:
        """allgather: optional host collective (fleet.all_gather) used to
        union the pass key set across processes — each process feeds its own
        data files but every process must agree on the global per-shard key
        lists (the role the shared PS plays in the reference's feed pass,
        box_wrapper.h:1201-1278)."""
        if not self._in_feed_pass:
            raise RuntimeError("end_feed_pass without begin_feed_pass")
        local = (np.unique(np.concatenate(self._feed_keys))
                 if self._feed_keys else np.empty(0, np.uint64))
        if allgather is not None:
            parts = allgather(local)
            allk = np.unique(np.concatenate(
                [np.asarray(p, np.uint64) for p in parts]))
        else:
            allk = local
        # policy-owned shard assignment (round 13): key-mod reproduces
        # allk % P bit-for-bit; selecting by mask keeps each shard's
        # list sorted (allk is sorted)
        shard = self.policy.shard_of(allk)
        self._shard_keys = []
        for s in range(self.num_shards):
            ks = allk[shard == s]
            if ks.size > self.shard_cap - 1:
                raise RuntimeError(
                    f"shard {s} working set {ks.size} exceeds shard capacity "
                    f"{self.shard_cap} (raise TableConfig.pass_capacity)")
            self._shard_keys.append(ks)
        # the replicated hot tier (2d-grid) freezes HERE — the one
        # boundary where every rank agrees on the global key set; the
        # rank-local sketches merge over the same collective first so
        # the frozen hot sets are cluster-identical
        if allgather is not None and self.policy.wants_observe:
            self.policy.merge_observations(allgather)
        self.policy.freeze_hot(self._shard_keys)
        self._drop_route_index()
        # native pass index (key → slab-local id hash map): built once here,
        # amortized over every batch of the pass
        from paddlebox_tpu.native.build import create_route_index
        self._route_index = create_route_index(self._shard_keys)
        self._feed_keys = []
        self._in_feed_pass = False
        self._overflow_warned = False  # fresh warning budget per pass

    @staticmethod
    def _incremental() -> bool:
        from paddlebox_tpu.config import flags
        return bool(flags.get_flag("incremental_pass"))

    def _staged_rows_for(self, missing: np.ndarray, rows: np.ndarray
                         ) -> np.ndarray:
        """Fill `rows` from the preload promote stage where possible;
        returns the mask of positions still needing a store read."""
        from paddlebox_tpu.embedding.pass_table import sorted_member
        need = np.ones(missing.size, bool)
        if self._staged_sh is not None and not self._test_mode:
            skeys, srows = self._staged_sh
            pos, hit = sorted_member(skeys, missing)
            if hit.any():
                rows[hit] = srows[pos[hit]]
                need = ~hit
                stat_add("pass_rows_promote_prefetched", int(hit.sum()))
        return need

    def _build_one(self, s: int) -> np.ndarray:
        """One shard's BeginPass promote. Incremental mode reuses the
        host residency cache for keys that were in the last pass (pure
        numpy row moves) and reads only NEW keys from the store —
        compaction instead of reallocation; the tail beyond the working
        set zeroes either way (never a full-capacity memset)."""
        C, W = self.shard_cap, self.layout.width
        ks = self._shard_keys[s]
        n = ks.size
        slab = np.empty((C, W), dtype=np.float32)
        store = self.stores[s]  # boxlint: disable=BX401 (ref-grab; uses below are locked)
        res_k = self._res_keys.get(s)
        base = self._res_rows.get(s)
        if (self._incremental() and res_k is not None and base is not None
                and store is not None and n):
            from paddlebox_tpu.embedding.pass_table import sorted_member
            pos, hit = sorted_member(res_k, ks)
            slab[:n][hit] = base[pos[hit]]
            miss = ks[~hit]
            rows = np.empty((miss.size, W), np.float32)
            need = self._staged_rows_for(miss, rows)
            if need.any():
                with self.store_lock:
                    got = (store.lookup(miss[need]) if self._test_mode
                           else store.lookup_or_create(miss[need]))
                rows[need] = got
            slab[:n][~hit] = rows
            # journal the promote delta: lookup_or_create CREATES missing
            # features (init rows the touched write-back may never
            # revisit) — replay must see them; re-recording store-present
            # non-resident rows is an idempotent upsert of equal bits
            if not self._test_mode:
                self._journal_rows(miss, rows)
            stat_add("pass_rows_promote_hit", int(hit.sum()))
            stat_add("pass_rows_promote_new", int(miss.size))
        elif n:
            if store is None:
                raise RuntimeError(f"shard {s} store not owned by this "
                                   "process")
            with self.store_lock:
                rows = (store.lookup(ks) if self._test_mode
                        else store.lookup_or_create(ks))
            slab[:n] = rows
            # full build: every shard key may have been created just now
            if not self._test_mode:
                self._journal_rows(ks, rows)
        slab[n:] = 0.0
        if self._incremental() and not self._test_mode and store is not None:
            # the cache tracks what the store holds for this pass's rows;
            # end-of-pass delta writeback refreshes only touched entries
            self._res_keys[s] = ks
            self._res_rows[s] = slab
        return slab

    def _begin_pass_state(self) -> None:
        """Per-pass promote bookkeeping shared by both build entry points:
        allocate the touched bitmaps (train mode, incremental only) and
        consume the staged promote rows."""
        self._touch_seen = False
        if self._incremental():
            if not self._test_mode:
                self._touched_sh = {s: np.zeros(self.shard_cap, bool)
                                    for s in self.owned_shards}
            else:
                self._touched_sh = None
        else:
            self._touched_sh = None
            # with the flag off the caches stop being maintained — drop
            # them now or a later re-enable would delta-build from stale
            # rows (PassTable's non-incremental end_pass does the same)
            self.invalidate_residency()

    def build_slabs(self) -> np.ndarray:
        """BeginPass: promote all shards' working sets → [P, C, W] host array
        (caller device_puts it with the mesh sharding). Single-process only
        — multi-process callers use build_owned_slabs."""
        if self._shard_keys is None:
            raise RuntimeError("build_slabs before feed pass completed")
        self._begin_pass_state()
        # promote boundary: the host residency mirror (_res_rows) stays
        # f32; only the DEVICE-bound copy encodes (identity for f32)
        out = encode_slab_rows_np(
            np.stack([self._build_one(s) for s in range(self.num_shards)]),
            self.layout)
        if not self._test_mode:
            self._staged_sh = None
        return out

    def build_owned_slabs(self) -> np.ndarray:
        """[len(owned), C, W] for this process's shards, in owned order —
        the process-local piece of the global [P, C, W] array
        (jax.make_array_from_process_local_data)."""
        if self._shard_keys is None:
            raise RuntimeError("build_owned_slabs before feed pass completed")
        self._begin_pass_state()
        out = encode_slab_rows_np(
            np.stack([self._build_one(s) for s in self.owned_shards]),
            self.layout)
        if not self._test_mode:
            self._staged_sh = None
        return out

    def note_touched(self, dest: int, uids: np.ndarray) -> None:
        """OR one push's dedup'd local ids into destination shard `dest`'s
        touched bitmap (stage_push_dedup calls this per staged step).
        Padding uids (>= shard_cap) drop; the trash row is cleared at
        writeback. Idempotent True stores — stager-thread safe. The delta
        writeback engages only if at least one mark arrived this pass —
        raw-slab callers that push outside the staged path (probes,
        oracle tests) still get the full writeback."""
        t = self._touched_sh
        if t is None:
            return
        m = t.get(dest)
        if m is None:
            return
        m[uids[uids < self.shard_cap]] = True
        self._touch_seen = True

    def _touched_idx(self, s: int, n: int) -> Optional[np.ndarray]:
        """Touched row indices within [0, n) for shard s, or None when the
        pass ran without touched accounting (full writeback required)."""
        t = self._touched_sh
        if t is None or not self._touch_seen:
            return None
        m = t.get(s)
        if m is None:
            return None
        m[self.shard_cap - 1] = False  # trash row never reaches the store
        return np.nonzero(m[:n])[0]

    def write_back(self, slabs: np.ndarray) -> None:
        """EndPass: [P, C, W] host array → shard stores (single process).
        Incremental mode writes back only touched rows per shard."""
        if self._test_mode:
            self._touched_sh = None
            return
        for s, ks in enumerate(self._shard_keys or []):
            if ks.size and self.stores[s] is not None:  # boxlint: disable=BX401 (presence probe)
                self._write_back_rows(s, ks, slabs[s])
        self._touched_sh = None

    def _write_back_rows(self, s: int, ks: np.ndarray,
                         slab_host: np.ndarray) -> None:
        """Store one shard's end-of-pass rows from a HOST [C, W] array:
        touched delta when the pass accounted touches, full otherwise.
        slab_host carries the DEVICE layout (encoded u16 under the bf16
        diet) — the writeback boundary decodes here, so the stores and
        the f32 residency mirror never see encoded bits."""
        slab_host = decode_slab_rows_np(slab_host, self.layout)
        idx = self._touched_idx(s, ks.size)
        if idx is None:
            # slab_host[:n] is a view — append_rows copies only when a
            # journal is actually attached
            self._journal_rows(ks, slab_host[:ks.size])
        with self.store_lock:
            if idx is None:
                self.stores[s].write_back(ks, slab_host[:ks.size])
                if self._incremental():
                    self._res_keys[s] = ks
                    self._res_rows[s] = np.array(slab_host)
                else:
                    # flag off mid-pass: this cache entry is no longer
                    # maintained — a stale read on re-enable is corruption
                    self._res_keys.pop(s, None)
                    self._res_rows.pop(s, None)
            else:
                if idx.size:
                    rows = np.ascontiguousarray(slab_host[idx])
                    # ONE gather serves both (journal-less runs pay no
                    # extra copy; the journal's own lock is leaf-level,
                    # no path back into store_lock)
                    self._journal_rows(ks[idx], rows)
                    self.stores[s].write_back(ks[idx], rows)
                    cache = self._res_rows.get(s)
                    if cache is not None:
                        cache[idx] = rows
                stat_add("pass_rows_written_back", int(idx.size))
                stat_add("pass_rows_writeback_skipped",
                         int(ks.size) - int(idx.size))

    def write_back_shard(self, s: int, slab: np.ndarray) -> None:
        """EndPass for ONE owned shard: [C, W] device-fetched slab → store
        (multi-process path: each process writes only its addressable
        shards)."""
        if self._test_mode:
            return
        ks = self._shard_keys[s]
        if ks.size:
            self._write_back_rows(s, ks, slab)

    def _write_back_shard_dev(self, s: int, dev) -> None:
        """EndPass for one shard straight from its single-device [1, C, W]
        buffer: with touched accounting, gather + D2H ONLY the touched
        rows (the incremental lifecycle's delta transfer); otherwise the
        classic full-shard fetch."""
        ks = self._shard_keys[s]
        if not ks.size or self.stores[s] is None:  # boxlint: disable=BX401 (presence probe)
            return
        from paddlebox_tpu.obs.device import account_d2h
        idx = self._touched_idx(s, ks.size)
        if idx is None:
            full = np.asarray(dev)[0]
            account_d2h(full.nbytes)  # full-shard D2H
            self.write_back_shard(s, full)
            return
        if idx.size:
            import jax.numpy as jnp
            dev_rows = np.asarray(jnp.asarray(dev)[0][jnp.asarray(idx)])
            account_d2h(dev_rows.nbytes)  # touched-row delta D2H
            rows = decode_slab_rows_np(dev_rows, self.layout)
            self._journal_rows(ks[idx], rows)
            with self.store_lock:
                self.stores[s].write_back(ks[idx], rows)
            cache = self._res_rows.get(s)
            if cache is not None:
                cache[idx] = rows
        stat_add("pass_rows_written_back", int(idx.size))
        stat_add("pass_rows_writeback_skipped",
                 int(ks.size) - int(idx.size))

    def write_back_addressable(self, slabs) -> None:
        """EndPass over a jax [P, C, W] global array: dump THIS process's
        addressable shards (the one owner of the shard-index-from-
        addressable-shard idiom — trainers call this instead of walking
        .addressable_shards themselves). With touched accounting only the
        touched rows cross the device→host wire; single-process callers
        get the same delta through end_pass_write_back."""
        if self._test_mode:
            self._touched_sh = None
            return
        for sh in slabs.addressable_shards:
            pos = sh.index[0]
            s = (pos.start or 0) if isinstance(pos, slice) else int(pos)
            self._write_back_shard_dev(int(s), sh.data)
        self._touched_sh = None

    def end_pass_write_back(self, slabs) -> None:
        """Single-process EndPass over the device [P, C, W] global array:
        per-shard touched-row gather + D2H (all shards are addressable in
        one process, so this shares write_back_addressable's path). The
        pre-incremental equivalent was write_back(np.asarray(slabs)) — a
        full-slab transfer every pass."""
        self.write_back_addressable(slabs)

    def invalidate_residency(self) -> None:
        """Drop the per-shard residency caches and staged promote rows.
        Must follow ANY store mutation outside the pass cadence (aging,
        shrink decay, spill, checkpoint stat rewrites, load) — the next
        build falls back to full store reads."""
        self._res_keys = {}
        self._res_rows = {}
        self._staged_sh = None

    # ------------------------------------------------- preload promote hooks
    def promote_prefetch_ctx(self):
        """(known_fn, store_facade, lock) for preload.PromotePrefetcher,
        or None (flag off, test mode, no active pass). The facade routes
        lookup_present by key % P over the owned shards; shards whose
        store lacks lookup_present (e.g. PS-backed) report found=False and
        fall through to the boundary's lookup_or_create."""
        from paddlebox_tpu.config import flags
        if (not flags.get_flag("incremental_pass")
                or not flags.get_flag("preload_promote")
                or self._test_mode or self._shard_keys is None):
            return None
        if not any(st is not None and hasattr(st, "lookup_present")
                   for st in self.stores):  # boxlint: disable=BX401 (capability probe, pre-handoff)
            return None
        # numpy snapshot diff, NOT the native route index: the index
        # handle can be destroyed by an interleaved eval pass while the
        # prefetch thread is mid-probe; the arrays stay alive here
        snapshot = [np.asarray(k) for k in self._shard_keys]
        policy = self.policy

        def known(keys: np.ndarray) -> np.ndarray:
            from paddlebox_tpu.embedding.pass_table import sorted_member
            out = np.zeros(keys.size, bool)
            shard = policy.shard_of(keys)
            for s in range(self.num_shards):
                m = shard == s
                if m.any():
                    out[m] = sorted_member(snapshot[s], keys[m])[1]
            return out

        return known, _ShardLookupFacade(self), self.store_lock

    def accept_staged_rows(self, keys: np.ndarray, rows: np.ndarray) -> None:
        """Install the promote stager's prefetched (key, row) pairs for the
        next train build. keys must be sorted unique."""
        if keys.size:
            self._staged_sh = (keys, rows)

    @property
    def test_mode(self) -> bool:
        return self._test_mode

    def set_test_mode(self, test: bool) -> None:
        self._test_mode = test

    @property
    def pass_size(self) -> int:
        return sum(k.size for k in self._shard_keys or [])

    # ---------------------------------------------------------- batch index
    def bucketize(self, keys: np.ndarray, valid: np.ndarray) -> ShardedBatchIndex:
        """Route one batch's keys: shard = policy.shard_of(key) (key-mod
        default = split_input_to_shard, heter_comm_inl.h:1117), local id
        by searchsorted in the shard's sorted pass key list, batch-level
        dedup into bucket slots.

        Native route.cc when built (pass-indexed hash, ~13M keys/sec at
        the reference's 1800×2048 budget): the key-mod policy keeps the
        legacy rt_bucketize (identical code path = pre-policy
        bit-parity); every other policy pre-mixes its per-key shard
        array vectorized and runs rt_bucketize_sharded — the native
        dedup/bucket loop at rate under any routing. Vectorized numpy
        fallback (the host analog of the reference's on-device
        dedup_keys_and_fillidx, heter_comm_inl.h:2231; the round-1
        per-key dict loop managed ~0.5M).
        Mutates `valid` in place to drop occurrences of overflowed keys.
        WHICH keys overflow when a shard bucket fills is unspecified (native
        drops late first-occurrences, numpy drops the largest key values) —
        size bucket_cap so overflow never happens in normal operation."""
        if self._shard_keys is None:
            raise RuntimeError("no active pass key set")
        P, KB = self.num_shards, self.bucket_cap
        trash = self.shard_cap - 1
        buckets = np.full((P, KB), trash, dtype=np.int32)
        restore = np.zeros(keys.shape[0], dtype=np.int32)

        native = _route_lib()
        keymod = self.policy.native_keymod
        if (native is not None and self._route_index is not None
                and (keymod or hasattr(native, "rt_bucketize_sharded"))):
            import ctypes
            c = ctypes
            keys_c = np.ascontiguousarray(keys, dtype=np.uint64)
            if valid.dtype != np.bool_ or not valid.flags.c_contiguous:
                raise TypeError("valid must be a contiguous bool array")
            missing = np.zeros(1, np.uint64)
            if keymod:
                rc = native.rt_bucketize(
                    self._route_index,
                    keys_c.ctypes.data_as(c.POINTER(c.c_uint64)),
                    valid.view(np.uint8).ctypes.data_as(
                        c.POINTER(c.c_uint8)),
                    keys_c.size, P, KB,
                    buckets.ctypes.data_as(c.POINTER(c.c_int32)),
                    restore.ctypes.data_as(c.POINTER(c.c_int32)),
                    missing.ctypes.data_as(c.POINTER(c.c_uint64)))
            else:
                shard_c = np.ascontiguousarray(
                    self.policy.shard_of(keys_c), np.int32)
                rc = native.rt_bucketize_sharded(
                    self._route_index,
                    keys_c.ctypes.data_as(c.POINTER(c.c_uint64)),
                    shard_c.ctypes.data_as(c.POINTER(c.c_int32)),
                    valid.view(np.uint8).ctypes.data_as(
                        c.POINTER(c.c_uint8)),
                    keys_c.size, P, KB,
                    buckets.ctypes.data_as(c.POINTER(c.c_int32)),
                    restore.ctypes.data_as(c.POINTER(c.c_int32)),
                    missing.ctypes.data_as(c.POINTER(c.c_uint64)))
            if rc == -1:
                raise KeyError(
                    f"key {int(missing[0])} not registered in feed pass")
            if rc == -3:
                raise ValueError(
                    "sharding policy %s produced an out-of-range shard "
                    "for key %d" % (self.policy.name, int(missing[0])))
            if rc < 0:
                raise MemoryError("rt_bucketize scratch allocation failed")
            if rc:
                self._note_overflow(int(rc))
            return ShardedBatchIndex(buckets=buckets, restore=restore,
                                     overflow=int(rc))

        idx = np.nonzero(valid)[0]
        if idx.size == 0:
            return ShardedBatchIndex(buckets=buckets, restore=restore,
                                     overflow=0)
        uniq, inv = np.unique(keys[idx], return_inverse=True)
        shard = self.policy.shard_of(uniq).astype(np.int64)
        counts = np.bincount(shard, minlength=P)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        # uniq is sorted, so a stable sort by shard keeps keys sorted within
        # each shard group — groups are contiguous [starts[s], starts[s]+n)
        order = np.argsort(shard, kind="stable")
        rank = np.arange(uniq.size, dtype=np.int64) - starts[shard[order]]

        # per-unique-key slot (s*KB + rank) in np.unique order; overflow = -1
        slot_of_uniq = np.empty(uniq.size, dtype=np.int64)
        kept = rank < KB
        slot_of_uniq[order] = np.where(kept, shard[order] * KB + rank, -1)

        # local ids: one searchsorted per shard over its contiguous group
        for s in range(P):
            lo, n = starts[s], counts[s]
            group = uniq[order[lo:lo + n]]
            n_keep = min(int(n), KB)
            g = group[:n_keep]
            sk = self._shard_keys[s]
            pos = np.searchsorted(sk, g)
            if n_keep and (pos.max(initial=0) >= sk.size
                           or not np.array_equal(sk[pos], g)):
                if sk.size == 0:
                    missing = g[0]
                else:
                    bad = (pos >= sk.size) | (sk[np.minimum(
                        pos, sk.size - 1)] != g)
                    missing = g[bad][0]
                raise KeyError(f"key {missing} not registered in feed pass")
            buckets[s, :n_keep] = pos

        occ_slots = slot_of_uniq[inv]
        overflow = int((occ_slots < 0).sum())
        if overflow:
            valid[idx[occ_slots < 0]] = False
            self._note_overflow(overflow)
        restore[idx] = np.where(occ_slots >= 0, occ_slots, 0)
        return ShardedBatchIndex(buckets=buckets, restore=restore,
                                 overflow=overflow)

    def _note_overflow(self, count: int) -> None:
        """Bucket overflow means those keys' GRADIENTS ARE DROPPED this
        batch — never let that pass silently (the PADDLE_ENFORCE
        discipline, box_wrapper_impl.h:139): stat counter always, one
        warning per feed pass, and a hard error under the
        strict_bucket_overflow flag. Runs on stager threads — the warn
        latch race is at worst a double log line."""
        stat_add("sharded_bucket_overflow", count)
        from paddlebox_tpu.config import flags
        if flags.get_flag("strict_bucket_overflow"):
            raise RuntimeError(
                f"sharded bucket overflow: {count} keys dropped this "
                f"batch (bucket_cap={self.bucket_cap} too small for this "
                "key skew) — their gradients would be silently lost; "
                "raise bucket_cap or unset strict_bucket_overflow")
        if not self._overflow_warned:
            self._overflow_warned = True
            import logging
            logging.getLogger("paddlebox_tpu").warning(
                "sharded bucket overflow: %d keys dropped this batch "
                "(their gradients are LOST); bucket_cap=%d is too small "
                "for this key skew — further overflows this pass count "
                "in stats.sharded_bucket_overflow only", count,
                self.bucket_cap)

    def check_need_limit_mem(self) -> int:
        """Per-shard pass-cadence spill (CheckNeedLimitMem/ShrinkResource,
        box_wrapper.h:627-629); budget divides evenly across owned shards
        — except table-wide backends (PS-backed shards), which receive the
        WHOLE budget once through their primary. Any spill drops the
        incremental residency caches (rows left the stores)."""
        budget = self.config.ssd_max_resident_rows(self.layout.width)
        if budget is None:
            return 0
        per_shard = budget // max(1, len(self.owned_shards))
        total = 0
        unsound = 0
        # under the lock: a concurrent PromotePrefetcher lookup_present
        # must never observe a spill mid-flight (native stores have no
        # internal lock — arena rows move)
        with self.store_lock:
            for st in self.stores:
                if st is None or not hasattr(st, "spill"):
                    continue
                n = st.spill(budget if getattr(st, "spill_table_wide",
                                               False) else per_shard)
                total += n
                # local tier stores journal their own MV_SPILL records
                # via the sink; a store without one (PS-backed — the
                # tier lives server-side, invisible to this journal)
                # makes the epoch unreplayable
                if n and not hasattr(st, "set_journal_sink"):
                    unsound += n
        if total:
            self.invalidate_residency()
            if unsound and self._journal is not None:
                self._journal.taint(
                    f"{unsound} rows spilled on a server-side tier "
                    "(outside the journaled MOVE cadence)")
        return total

    def shrink_table(self) -> int:
        self.invalidate_residency()  # decay rewrites every store row
        with self.store_lock:
            n = sum(st.shrink() for st in self.stores if st is not None)
        from paddlebox_tpu.train.journal import EV_SHRINK
        self._journal_event(EV_SHRINK)
        return n

    def end_day(self, age: bool = True) -> int:
        """Day boundary over the owned shards: age unseen_days, then
        shrink (see PassTable.end_day for the age=False/save_base rule).
        PS-backed shards age server-side through their primary."""
        self.invalidate_residency()
        from paddlebox_tpu.train.journal import (EV_AGE_DAYS,
                                                 EV_TICK_SPILL_AGE)
        # event appends INSIDE the store_lock hold: a concurrent promote
        # prefetcher journals MV_FAULT_IN under the same lock, and replay
        # must see record order == mutation order (tier epoch parity)
        with self.store_lock:
            for st in self.stores:
                if st is None:
                    continue
                if age:
                    st.age_unseen_days()
                else:
                    st.tick_spill_age()
            self._journal_event(EV_AGE_DAYS if age else EV_TICK_SPILL_AGE)
        return self.shrink_table()

    # checkpoint boundary: the driver serializes save/load against
    # passes, so no prefetch thread can be live in these three
    def save(self, path_prefix: str) -> None:  # boxlint: disable=BX401
        for s, st in enumerate(self.stores):
            if st is not None:
                st.save(f"{path_prefix}.shard{s:03d}")

    def load(self, path_prefix: str) -> None:  # boxlint: disable=BX401
        self.invalidate_residency()
        if self._journal is not None:
            self._journal.taint("per-shard store load outside the "
                                "checkpoint plane")
        for s, st in enumerate(self.stores):
            if st is not None:
                st.load(f"{path_prefix}.shard{s:03d}")

    def load_ssd_to_mem(self) -> int:  # boxlint: disable=BX401
        """LoadSSD2Mem over the owned shards (box_wrapper.cc:1319)."""
        self.invalidate_residency()  # fault-in applies missed days
        return sum(st.load_spilled() for st in self.stores
                   if st is not None and hasattr(st, "load_spilled"))

    def store_view(self) -> "ShardedStoreView":
        """One store-shaped facade over the owned shards, so the
        CheckpointManager/run_day day cadence drives the sharded table
        with the same code as the single-host PassTable. PS-backed shards
        checkpoint server-side (PSClient.save) and reject this view."""
        from paddlebox_tpu.embedding.ps_store import PSBackedStore
        # type/presence probe only (checkpoint boundary; no row access)
        for st in self.stores:  # boxlint: disable=BX401
            if st is None:
                # a DONE-marked base model missing the non-owned shards'
                # rows would read as complete — fail here instead
                raise TypeError(
                    "store_view needs every shard local (single process); "
                    "multi-process jobs checkpoint per owned shard via "
                    "table.save()")
            if isinstance(st, PSBackedStore):
                raise TypeError("PS-backed shards checkpoint server-side "
                                "(PSClient.save), not through store_view")
        return ShardedStoreView(self)


class _ShardLookupFacade:
    """Single-store lookup_present view over a ShardedPassTable's owned
    shards (the preload promote stager's read interface): keys route by
    key % P; shards without lookup_present (non-owned, PS-backed) report
    found=False so those keys resolve at the pass boundary instead."""

    def __init__(self, table: "ShardedPassTable") -> None:
        self._table = table

    def lookup_present(self, keys: np.ndarray):
        t = self._table
        out = np.zeros((keys.size, t.layout.width), np.float32)
        found = np.zeros(keys.size, bool)
        shard = t.policy.shard_of(keys)
        for s in t.owned_shards:
            st = t.stores[s]
            if st is None or not hasattr(st, "lookup_present"):
                continue
            m = shard == s
            if m.any():
                out[m], found[m] = st.lookup_present(keys[m])
        return out, found


class ShardedStoreView:
    """state_items/write_back/spilled_snapshot/load over a
    ShardedPassTable's OWNED shard stores — the store protocol subset the
    checkpoint tier consumes. Keys route by the table's sharding POLICY
    (identical to the a2a routing), so a view round trip lands every row
    in its owning store — and a checkpoint written under one policy
    redistributes automatically when loaded under another (write_back/
    load route by the live policy, not the one that wrote the blob)."""

    def __init__(self, table: ShardedPassTable) -> None:
        self._table = table

    def _owned(self):
        return [(s, st) for s, st in enumerate(self._table.stores)
                if st is not None]

    def state_items(self) -> Tuple[np.ndarray, np.ndarray]:
        parts = [st.state_items() for _, st in self._owned()]
        keys = np.concatenate([k for k, _ in parts]) if parts else \
            np.empty(0, np.uint64)
        vals = (np.vstack([v for _, v in parts]) if parts else
                np.empty((0, self._table.layout.width), np.float32))
        return keys, vals

    def spilled_snapshot(self) -> Tuple[np.ndarray, np.ndarray]:
        ks, vs = [], []
        for _, st in self._owned():
            snap = getattr(st, "spilled_snapshot", None)
            if snap is None:
                continue
            k, v = snap()
            if k.size:
                ks.append(k)
                vs.append(v)
        if not ks:
            return (np.empty(0, np.uint64),
                    np.empty((0, self._table.layout.width), np.float32))
        return np.concatenate(ks), np.vstack(vs)

    def spilled_count(self) -> int:
        """Summed SSD-tier rows over the owned shards."""
        total = 0
        for _, st in self._owned():
            probe = getattr(st, "spilled_count", None)
            if probe is not None:
                total += probe()
        return total

    def spilled_keys(self) -> np.ndarray:
        """Every live tier key over the owned shards (save_base's anchor
        MV_SPILL record set)."""
        parts = []
        for _, st in self._owned():
            fn = getattr(st, "spilled_keys", None)
            if fn is not None:
                k = fn()
                if k.size:
                    parts.append(k)
        return (np.concatenate(parts) if parts
                else np.empty(0, np.uint64))

    def rebase_spill_ages(self) -> None:
        """Pin each owned shard tier's lazy-aging span boundary (the
        full-save anchor; see SpillTier.rebase)."""
        for _, st in self._owned():
            fn = getattr(st, "rebase_spill_ages", None)
            if fn is not None:
                fn()

    def write_back(self, keys: np.ndarray, values: np.ndarray) -> None:
        # checkpoint stat rewrites land here — the residency caches no
        # longer mirror the stores afterwards
        self._table.invalidate_residency()
        keys = np.asarray(keys, np.uint64)
        shard = self._table.policy.shard_of(keys)
        for s, st in self._owned():
            m = shard == s
            if m.any():
                st.write_back(keys[m], values[m])

    def update_stat_after_save(self, table_cfg, param: int) -> None:
        """Checkpoint stat rewrite, per shard in place (every shard
        store applies the same accessor rule to its own resident rows —
        routing is irrelevant, the union is the table)."""
        self._table.invalidate_residency()
        from paddlebox_tpu.train.journal import apply_stat_after_save
        for _, st in self._owned():
            apply_stat_after_save(st, table_cfg, param)

    def load(self, path: str) -> None:
        """Split a single checkpoint — columnar manifest (loaded through
        the reader pool) or legacy pickle, sniffed — across the shard
        stores; keys route by the LIVE sharding policy, so a checkpoint
        written under one policy redistributes on load under another."""
        from paddlebox_tpu.embedding.ckpt_store import load_sparse_any
        self.load_blob(load_sparse_any(path))

    def load_blob(self, blob: dict) -> None:
        """The post-deserialize half of load (their load_blob handles
        index reset, stale-spill clearing, and layout validation) — one
        blob split across shards without re-serializing."""
        self._table.invalidate_residency()
        keys = np.asarray(blob["keys"], np.uint64)
        shard = self._table.policy.shard_of(keys)
        for s, st in self._owned():
            m = shard == s
            st.load_blob(dict(blob, keys=keys[m], values=blob["values"][m]))
