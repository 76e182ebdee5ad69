"""Multi-host (multi-process) runtime: jax.distributed bootstrap, DCN-aware
mesh construction, and per-host data loading.

Equivalent of the reference's multi-node path — torch.distributed
init_process_group + rank/world env handling (megatron/initialize.py:124-167)
and the per-DP-rank batch slicing in its samplers (data_samplers.py:49-95).
On TPU pods the runtime discovers topology itself; explicit
coordinator/num_processes/process_id cover CPU tests and non-TPU clusters.

Design notes:
  * the mesh keeps ("data", "expert", "pipe", "context", "tensor") with tensor
    innermost (ICI-adjacent); across *slices* (DCN) only the data axis is
    split — create_hybrid_device_mesh puts the slice index outermost on
    the data axis, so gradient all-reduce is the only DCN collective,
    matching the scaling-book recipe and the reference's DP-over-IB layout.
  * each process feeds only its addressable shard of the global batch:
    host_batch_slice says which rows to load, put_process_local_batch
    assembles the global jax.Array from per-host data
    (jax.make_array_from_process_local_data).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatron_tpu.config import ParallelConfig
from megatron_tpu.parallel.mesh import MESH_AXES, MeshRuntime
from megatron_tpu.parallel.sharding import BATCH_AXES


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialize jax.distributed if this looks like a multi-process run.

    Resolution order: explicit args > MEGATRON_TPU_COORDINATOR /
    MEGATRON_TPU_NUM_PROCESSES / MEGATRON_TPU_PROCESS_ID env > TPU-pod
    auto-detection (bare initialize()). Returns True if distributed was
    initialized by this call.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "MEGATRON_TPU_COORDINATOR")
    if num_processes is None and "MEGATRON_TPU_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["MEGATRON_TPU_NUM_PROCESSES"])
    if process_id is None and "MEGATRON_TPU_PROCESS_ID" in os.environ:
        process_id = int(os.environ["MEGATRON_TPU_PROCESS_ID"])

    if coordinator_address is None and num_processes is None:
        # single-process unless launched on a TPU pod runtime that knows
        # its own topology (GKE/TPU-VM metadata). A ONE-host TPU machine
        # may export TPU_WORKER_HOSTNAMES too (one name): there is nobody
        # to rendezvous with, and a bare initialize() there would go
        # looking for a coordinator — so only a list of several hosts, or
        # the explicit opt-in, selects the pod path.
        hosts = [h for h in os.environ.get(
            "TPU_WORKER_HOSTNAMES", "").split(",") if h.strip()]
        if len(hosts) > 1 or os.environ.get(
                "MEGATRON_TPU_AUTO_DISTRIBUTED") == "1":
            try:
                jax.distributed.initialize()
            except (RuntimeError, ValueError):
                # best-effort: backend already initialized (tests,
                # notebooks), already distributed-initialized, or the env
                # advertises a pod without a resolvable coordinator —
                # stay single-process
                return False
            return True
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)
    return True


def _num_slices(devices) -> int:
    slice_ids = {getattr(d, "slice_index", 0) for d in devices}
    return len(slice_ids)


def build_multihost_mesh(parallel: ParallelConfig) -> MeshRuntime:
    """DCN-aware mesh over all global devices.

    Multi-slice (DCN-connected) topologies split only the data axis across
    slices: dcn shape (num_slices, 1, 1, 1, 1) x ici shape
    (dp/num_slices, ep, pp, cp, tp). Single-slice/multi-host-CPU falls back
    to the plain row-major mesh over jax.devices() (process-contiguous, so
    the data axis is outermost across hosts there too).
    """
    parallel = parallel.validate()
    devices = jax.devices()
    dp = parallel.derive_data_parallel(len(devices))
    n_slices = _num_slices(devices)
    shape = (dp, parallel.expert_parallel, parallel.pipeline_parallel,
             parallel.context_parallel, parallel.tensor_parallel)
    if n_slices > 1:
        if dp % n_slices:
            raise ValueError(
                f"data_parallel={dp} must be divisible by num_slices="
                f"{n_slices} (only the data axis spans DCN)")
        from jax.experimental import mesh_utils

        ici = (dp // n_slices,) + shape[1:]
        dcn = (n_slices, 1, 1, 1, 1)
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici, dcn, devices=devices)
        mesh = Mesh(dev_array, MESH_AXES)
    else:
        mesh = Mesh(np.asarray(devices).reshape(shape), MESH_AXES)
    return MeshRuntime(mesh=mesh, parallel=parallel, data_parallel=dp)


def host_batch_slice(rt: MeshRuntime, global_rows: int) -> Tuple[int, int]:
    """[start, stop) of global batch rows this process must load (the
    reference's per-DP-rank sampler offset, data_samplers.py:76-95)."""
    sh = NamedSharding(rt.mesh, P(BATCH_AXES))
    index_map = sh.devices_indices_map((global_rows,))
    mine = [sl[0] for d, sl in index_map.items()
            if d.process_index == jax.process_index()]
    if not mine:
        return (0, 0)
    starts = [0 if s.start is None else s.start for s in mine]
    stops = [global_rows if s.stop is None else s.stop for s in mine]
    return (min(starts), max(stops))


def put_process_local_batch(
    rt: MeshRuntime,
    local_batch: Dict[str, np.ndarray],
    global_rows: int,
) -> Dict[str, jax.Array]:
    """Assemble global batch arrays from this process's local rows
    (rows host_batch_slice told it to load)."""
    out = {}
    for k, v in local_batch.items():
        sh = NamedSharding(rt.mesh, P(BATCH_AXES))
        global_shape = (global_rows,) + tuple(v.shape[1:])
        out[k] = jax.make_array_from_process_local_data(sh, np.asarray(v),
                                                        global_shape)
    return out
