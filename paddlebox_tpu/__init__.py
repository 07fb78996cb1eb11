"""paddlebox_tpu: a TPU-native ultra-large-scale sparse CTR training framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of Baidu PaddleBox
(reference: shang1017/PaddleBox): a pod-sharded sparse embedding parameter
server with pass-cadenced HBM working sets and host-DRAM/SSD spill, exposed as
differentiable pull_sparse/push_sparse ops, an async multi-threaded data
pipeline, ICI-collective dense sync, streaming AUC metrics, and two-tier
(batch model + serving delta) checkpoints.

Layer map (TPU-native analog of reference SURVEY.md §1):
  models/     CTR model zoo (flax-free functional modules)      ~ L7 python API
  train/      trainer + pass loop + checkpoint                  ~ L5 trainer/worker runtime
  data/       slot records, parsers, packer, dataset            ~ L4 data pipeline
  ops/        sparse pull/push, seqpool+cvm, data_norm, ...     ~ L3 op library
  embedding/  sparse table: accessor, optimizers, pass slab,
              host store, sharded table                         ~ L2 BoxPS/HeterPS
  parallel/   mesh, collectives, ZeRO-1 sharding, pipeline,
              ring attention                                    ~ L1/§2.8 parallelism
  utils/      timers, stat registry, channels, flags            ~ L1 platform
"""

from paddlebox_tpu.version import __version__

# the package import stays jax-free (serving replicas, host tools)
from paddlebox_tpu.config import flags  # noqa: F401
