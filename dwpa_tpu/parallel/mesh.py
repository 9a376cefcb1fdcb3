"""Mesh construction and candidate-batch sharding.

The reference system's parallelism is volunteer data-parallelism over the
candidate keyspace (SURVEY.md §2.10: independent clients, dictionary
shards, coverage matrix).  On a TPU pod slice the same axis — candidates —
is the natural shard dimension: PBKDF2 is embarrassingly parallel per
candidate, so the hot loop needs *zero* cross-device traffic and only the
tiny found-flags tensor is ever reduced over ICI (psum in parallel/step.py).

One 1-D mesh axis ("dp") is therefore the whole story intra-pod; scaling
further mirrors the reference's WAN layer (many independent clients each
owning a pod slice), not a second mesh axis.
"""

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DP_AXIS = "dp"


def default_mesh(devices=None, n: int = None) -> Mesh:
    """A 1-D data-parallel mesh over ``devices`` (default: all present)."""
    if devices is None:
        devices = jax.devices()
    if n is not None:
        devices = devices[:n]
    return Mesh(np.asarray(devices), (DP_AXIS,))


def _shard_batch_axis(mesh: Mesh, x, spec: P):
    """Place ``x`` with its leading axis split over the dp mesh axis.

    Single-process: ``x`` is the whole batch, placed under the sharding.
    Multi-process (a ``multihost_mesh`` spanning hosts): ``x`` is this
    host's *local* shard, assembled into the global array with
    ``jax.make_array_from_process_local_data`` — device_put cannot
    express "local slice of a global array" across non-addressable
    devices.
    """
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sharding, np.asarray(x))
    return jax.device_put(x, sharding)


def shard_candidates(mesh: Mesh, pw_words):
    """Place a packed [B, 16] candidate batch with B split over the mesh
    (see ``_shard_batch_axis`` for the single-/multi-process contract)."""
    return _shard_batch_axis(mesh, pw_words, P(DP_AXIS, None))


def shard_vector(mesh: Mesh, v):
    """The [B]-shaped companion of ``shard_candidates`` (e.g. word
    lengths), same contract."""
    return _shard_batch_axis(mesh, v, P(DP_AXIS))


def multihost_mesh(coordinator: str = None, num_processes: int = None,
                   process_id: int = None, auto_init: bool = False) -> Mesh:
    """A 1-D dp mesh spanning every chip of a multi-host slice.

    The distributed backend analog of the reference's NCCL/MPI role
    (SURVEY.md §5.8): ``jax.distributed.initialize`` wires the hosts
    (args default to the TPU environment's auto-detection), and the mesh
    covers ``jax.devices()`` — the *global* device list — so the same
    shard_map crack step scales from one chip to a full slice unchanged.
    Because the candidate axis is the only sharded axis and the hot loop
    is traffic-free, the lone collective (the psum hits-gate) rides ICI
    intra-host and DCN across hosts; its payload is one scalar per batch,
    so DCN latency is irrelevant to throughput.

    Each host feeds its local shard via ``shard_candidates`` (which
    assembles host-local slices into the global array with
    ``jax.make_array_from_process_local_data``); work-unit distribution
    stays on the reference's HTTP/JSON WAN protocol — a multi-host slice
    is simply one very large volunteer.
    """
    kw = {}
    if coordinator is not None:
        kw["coordinator_address"] = coordinator
    if num_processes is not None:
        kw["num_processes"] = num_processes
    if process_id is not None:
        kw["process_id"] = process_id
    # ``auto_init``: join with zero args, letting jax auto-detect the
    # cluster from the managed environment (TPU pod slices) — the one
    # blessed slice-join path for callers with no explicit topology
    # (client CLI --multihost).  Either way the init must run before
    # anything touches the XLA backend (even jax.process_count() would
    # initialise it), hence the check against the distributed-service
    # state rather than device APIs.
    if (auto_init or kw) and not jax.distributed.is_initialized():
        jax.distributed.initialize(**kw)
    return Mesh(np.asarray(jax.devices()), (DP_AXIS,))
