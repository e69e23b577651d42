"""Parallelism over TPU meshes (SURVEY §2.5 — the kvstore/NCCL/ps-lite stack
re-expressed as SPMD sharding + XLA collectives over ICI/DCN).

- mesh:              device mesh construction (dp/tp/pp/sp/ep axes)
- data_parallel:     sharded fused train step (≙ dist_device_sync kvstore)
- tensor_parallel:   row/col-sharded layers (NEW capability vs reference)
- ring_attention:    sequence/context parallelism over the ring (NEW)
- pipeline:          GPipe ring + hand-scheduled 1F1B pipeline (NEW)
- pipeline_interleaved: virtual-stage (interleaved) 1F1B — static greedy
                     tick tables, schedule-bounded stash; measured
                     disposition in docs/PERF_PIPELINE.md (NEW)
- moe:               expert parallel mixture-of-experts (NEW)
- compression:       2-bit gradient compression analog (ref gradient_compression.h)
"""
from jax import shard_map  # noqa
from .mesh import make_mesh, current_mesh, set_current_mesh, replicated, shard_spec  # noqa
from .data_parallel import DataParallelTrainStep  # noqa
from .tensor_parallel import ColParallelDense, RowParallelDense, shard_params  # noqa
from .ring_attention import ring_attention, local_attention  # noqa
from .ulysses import ulysses_attention  # noqa
from .pipeline import PipelineParallel, pipeline_spmd, pipeline_1f1b_grads  # noqa
from .pipeline_interleaved import (  # noqa
    pipeline_interleaved_grads, interleaved_schedule, schedule_stats)
from .gluon_pipeline import PipelineStack  # noqa
from .moe import MoELayer, load_balancing_loss, router_z_loss  # noqa
from .compression import GradientCompression  # noqa
from .dist import init_distributed, rank, num_workers  # noqa
