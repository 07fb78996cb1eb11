"""Typed structured configs.

TPU-native analog of the reference's proto tier: DataFeedDesc
(paddle/fluid/framework/data_feed.proto), TrainerDesc + BoxPSWorkerParameter
(framework/trainer_desc.proto:78,121-129), sparse-optimizer hyperparameters
(framework/fleet/heter_ps/optimizer_conf.h:20-45) and CTR accessor thresholds
(distributed/ps/table/ctr_accessor.{h,cc}). Dataclasses instead of protobuf:
they are hashable/static-friendly for jit closure capture.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from paddlebox_tpu.config import flags


@dataclasses.dataclass(frozen=True)
class SparseOptimizerConfig:
    """Hyperparameters of the in-table sparse optimizer.

    Field names and defaults mirror heter_ps/optimizer_conf.h:20-45 so configs
    written against the reference carry over unchanged.
    """

    # embed_w (the 1-d "lr" weight) SGD
    nonclk_coeff: float = 0.1
    clk_coeff: float = 1.0
    min_bound: float = -10.0
    max_bound: float = 10.0
    learning_rate: float = 0.05
    initial_g2sum: float = 3.0
    initial_range: float = 0.0
    beta1_decay_rate: float = 0.9
    beta2_decay_rate: float = 0.999
    ada_epsilon: float = 1e-8
    # embedx (the mf_dim-wide factor vector)
    mf_create_thresholds: float = 10.0
    mf_learning_rate: float = 0.05
    mf_initial_g2sum: float = 3.0
    mf_initial_range: float = 1e-4
    mf_beta1_decay_rate: float = 0.9
    mf_beta2_decay_rate: float = 0.999
    mf_min_bound: float = -10.0
    mf_max_bound: float = 10.0
    mf_ada_epsilon: float = 1e-8
    nodeid_slot: int = 9008
    feature_learning_rate: float = 0.05
    optimizer: str = "adagrad"  # adagrad | adam | adam_shared | naive


@dataclasses.dataclass(frozen=True)
class TableConfig:
    """Sparse embedding table shape + lifecycle policy.

    embedx_dim mirrors BoxPS ``embedx_dim`` (box_wrapper.h:650 GetInsEx arg);
    decay/shrink thresholds mirror CtrCommonAccessor (ctr_accessor.cc:63-79).
    """

    embedx_dim: int = 8                  # factor width (pull returns 1+embedx ... cvm adds 2)
    expand_embed_dim: int = 0            # second table for NN-cross (pull_box_extended_sparse)
    pass_capacity: int = 1 << 20         # max unique keys resident per pass (HBM slab rows)
    value_dtype: str = "float32"
    # accessor lifecycle (ctr_accessor semantics)
    show_click_decay_rate: float = 0.98
    delete_threshold: float = 0.8
    delete_after_unseen_days: float = 30.0
    base_threshold: float = 1.5
    delta_threshold: float = 0.25
    delta_keep_days: float = 16.0
    optimizer: SparseOptimizerConfig = dataclasses.field(
        default_factory=SparseOptimizerConfig)
    # host/SSD tiering
    host_shard_bits: int = 6             # host store sharded into 2**bits locks
    ssd_dir: Optional[str] = None        # spill tier directory; None = DRAM only
    ssd_threshold_mb: float = 0          # spill host values beyond this budget

    def ssd_max_resident_rows(self, row_width: int) -> Optional[int]:
        """DRAM row budget for the pass-cadence limiter
        (CheckNeedLimitMem, box_wrapper.h:627-629); None = no limit.
        Fractional MB budgets are honored (small-scale tests)."""
        if not self.ssd_dir or not self.ssd_threshold_mb:
            return None
        return int(self.ssd_threshold_mb * (1 << 20)) // (row_width * 4)


@dataclasses.dataclass(frozen=True)
class SlotConfig:
    """One feature slot (DataFeedDesc.multi_slot_desc.slots entry)."""

    name: str
    type: str = "uint64"     # uint64 (sparse feasign) | float (dense)
    dim: int = 1             # dense dim for float slots
    is_used: bool = True
    max_len: int = 64        # per-instance value cap used for static batch packing


@dataclasses.dataclass(frozen=True)
class DataFeedConfig:
    """Analog of DataFeedDesc proto (data_feed.proto) + packer capacities."""

    slots: Tuple[SlotConfig, ...] = ()
    batch_size: int = 512
    pipe_command: str = ""               # optional preprocessing pipe, like ref pipe_command
    parser: str = "multislot"            # multislot text | binary archive
    rank_offset: bool = False            # emit pv rank-offset matrix (join phase)
    # per-task label slots for multi-task models: (task_name, slot_name)
    # pairs; tasks not listed fall back to the primary click label
    # (MMoE/ESMM train each head on its own label, metrics.h MultiTask)
    task_label_slots: Tuple[Tuple[str, str], ...] = ()
    # static capacity of flattened sparse keys per batch; 0 = batch*avg heuristic
    batch_key_capacity: int = 0
    # lines start with the instance id string (SlotRecordInMemoryDataFeed
    # parse_ins_id_); the id keys dump-field lines and InputTable aux-row
    # translation (InputTableDataFeed, data_feed.h:2221-2252)
    parse_ins_id: bool = False

    def used_sparse_slots(self) -> List[SlotConfig]:
        return [s for s in self.slots if s.is_used and s.type == "uint64"]

    def used_dense_slots(self) -> List[SlotConfig]:
        return [s for s in self.slots if s.is_used and s.type == "float"]

    def key_capacity(self, batch_size: Optional[int] = None) -> int:
        if self.batch_key_capacity:
            return self.batch_key_capacity
        override = int(flags.get_flag("padbox_max_batch_keys"))
        if override:
            return override
        bs = batch_size or self.batch_size
        per_ins = sum(min(s.max_len, 16) for s in self.used_sparse_slots())
        return max(128, bs * max(per_ins, 1))


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device mesh layout. Axes follow jax.sharding.Mesh conventions."""

    data: int = 1        # data-parallel axis size ("dp")
    model: int = 1       # table-shard / tensor axis size ("mp")
    pipeline: int = 1    # pipeline stages ("pp")
    axis_names: Tuple[str, ...] = ("data", "model")


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    """Two-tier, pass-cadenced checkpoints (SaveBase/SaveDelta semantics,
    box_wrapper.cc:1286-1318)."""

    batch_model_dir: str = "ckpt/batch"
    xbox_model_dir: str = "ckpt/xbox"
    save_delta_every_passes: int = 1
    save_base_every_days: int = 1
    async_save: bool = True


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """Analog of TrainerDesc + BoxPSWorkerParameter (trainer_desc.proto:78,121-129)."""

    # TrainerDesc compat (STRUCTURAL NO-OP): the reference's device-worker
    # thread count. Here the mesh defines device concurrency (one shard_map
    # program) and host staging parallelism comes from the stack_threads /
    # stream_depth flags — accepted so TrainerDesc configs carry over,
    # never consulted.
    thread_num: int = 1
    sync_mode: str = "step"              # step | k_step | async | sharding
    sync_weight_step: int = 1            # K in K-step dense sync
    # one flat allreduce ring across ALL devices even on a 2D (node, chip)
    # mesh, instead of the hierarchical RS/psum/AG split (the reference's
    # sync_one_ring_ TrainerDesc knob, boxps_worker.cc SyncParam)
    sync_one_ring: bool = False
    async_mode: bool = False             # host async dense table
    sharding: bool = False               # ZeRO-1 dense param partitioning
    dump_fields: Tuple[str, ...] = ()
    dump_fields_path: str = ""
    dump_thread_num: int = 1
    dense_lr: float = 1e-3
    dense_optimizer: str = "adam"
    # default from the check_nan_inf env flag (FLAGS_check_nan_inf)
    check_nan_inf: bool = dataclasses.field(
        default_factory=lambda: bool(flags.get_flag("check_nan_inf")))
    profile: bool = False
    scan_chunk: int = 8                  # batches fused per device dispatch
                                         # (lax.scan megastep); 1 = off
    # dense-tower compute dtype: "float32" | "bfloat16" (mixed precision —
    # params/optimizer state stay f32, matmuls run bf16 on the MXU; bf16
    # keeps f32's exponent range so CTR losses need no loss scaling)
    compute_dtype: str = "float32"
    # sharded-trainer pull/push all_to_all payload dtype: "float32" |
    # "bfloat16". bf16 halves the ICI bytes of the two value a2as (the
    # walk_to_src/walk_to_dest traffic); the in-table optimizer still
    # merges and updates in f32 (grads upcast after transport). The slab
    # and its state columns are untouched — only the wire format changes.
    a2a_dtype: str = "float32"
