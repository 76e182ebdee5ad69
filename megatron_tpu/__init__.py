"""megatron_tpu: a TPU-native LLM training framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of
epfLLM/Megatron-LLM (reference layout documented in SURVEY.md): 3D-parallel
(DP x PP x TP) + sequence/context-parallel training and finetuning of
GPT / Llama / Llama-2 / CodeLlama / Falcon / Mistral model families, with
mixed precision, a ZeRO-1-style sharded optimizer, instruction tuning,
HF weight interop, and an incremental-decoding inference service.

Design principles (TPU-first, not a port):
  * One ``jax.sharding.Mesh`` with axes ("data", "pipe", "context", "tensor")
    replaces the reference's NCCL process groups
    (ref: megatron/core/parallel_state.py).
  * Parallel linears are sharded einsums under GSPMD; XLA inserts and
    overlaps the collectives the reference hand-writes in
    megatron/core/tensor_parallel/{layers,mappings}.py.
  * Pipeline parallelism is shard_map + ppermute microbatch rotation
    (ref: megatron/schedules.py 1F1B).
  * Mutable global state (get_args(), parallel_state, rng tracker) becomes
    typed config dataclasses and threaded PRNG keys.
"""

__version__ = "0.1.0"

from megatron_tpu.config import (
    ModelConfig,
    ParallelConfig,
    OptimizerConfig,
    TrainingConfig,
)
from megatron_tpu.parallel.mesh import MeshRuntime, build_mesh

__all__ = [
    "ModelConfig",
    "ParallelConfig",
    "OptimizerConfig",
    "TrainingConfig",
    "MeshRuntime",
    "build_mesh",
    "__version__",
]
