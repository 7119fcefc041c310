"""Process bootstrap and rendezvous.

TPU-native replacement for both forms of the reference's ``ddp_setup``:

* explicit-rank form — ``MASTER_ADDR``/``MASTER_PORT`` env +
  ``init_process_group("nccl", rank, world_size)`` (reference ``multigpu.py:12-20``);
* env-driven (torchrun) form — bare ``init_process_group("nccl")`` with topology
  from ``RANK``/``WORLD_SIZE``/``MASTER_*`` env vars (reference
  ``multigpu_torchrun.py:12-13``, ``multinode_torchrun.py:12-13``).

Here both collapse onto ``jax.distributed.initialize`` against a coordinator
(process 0's address — the moral equivalent of ``head_node_ip:29500`` in
``slurm/sbatch_run.sh:12,22``). Env vars understood, mirroring the torchrun
contract one-to-one:

=================  =======================  =================================
torchrun env       ours                     meaning
=================  =======================  =================================
MASTER_ADDR:PORT   COORDINATOR_ADDRESS      host:port of process 0
WORLD_SIZE         NUM_PROCESSES            number of host processes
RANK               PROCESS_ID               this process's global id
LOCAL_RANK         (none needed)            JAX owns local device binding
=================  =======================  =================================

On a real TPU pod slice none of these are required: ``jax.distributed
.initialize()`` autodetects topology from the TPU metadata server, so
``setup_distributed()`` with no env set simply does the right thing.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

_initialized = False


def setup_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialize multi-process JAX. Safe to call in single-process runs (no-op).

    Explicit args take priority; otherwise ``COORDINATOR_ADDRESS`` /
    ``NUM_PROCESSES`` / ``PROCESS_ID`` env vars are used (torchrun-style);
    otherwise, if neither is present, this is a single-process run and we skip
    initialization entirely (the serial rung needs no rendezvous, like
    ``single_gpu.py``).
    """
    global _initialized
    if _initialized:
        return

    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if num_processes is None and "NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])

    if coordinator_address is None and num_processes is None:
        if not _on_tpu_pod():
            return  # serial / single-process: nothing to rendezvous
        # Real TPU pod slice: initialize() autodetects topology from the TPU
        # metadata server (the torchrun-env machinery has no analog here).
        jax.distributed.initialize()
    else:
        # Multi-process on the forced-CPU test rig: XLA's CPU client refuses
        # cross-process computations unless a collectives transport is
        # selected (gloo ships in jaxlib). Must be set before the backend
        # initializes; never touched on real TPU.
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    _initialized = True


def _on_tpu_pod() -> bool:
    """True when this process is one worker of a multi-HOST TPU slice: the
    TPU runtime's ``TPU_WORKER_HOSTNAMES`` names more than one worker.

    Only then is ``jax.distributed.initialize()``'s autodetection wanted.
    A single host exports the same family of variables
    (``TPU_WORKER_ID=0``, ``TPU_WORKER_HOSTNAMES=localhost``, sometimes a
    worker id alone); there the call has nothing to rendezvous with and,
    with no metadata server in reach, would wait on one — so anything
    short of a list of several hosts is a single-process run.
    """
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hostnames.split(",") if h.strip()]) > 1


def shutdown_distributed() -> None:
    """Tear down the coordination service (twin of ``destroy_process_group()``,
    reference ``multigpu.py:88``)."""
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def is_main_process() -> bool:
    """True on process 0 — the single checkpoint writer.

    Replaces the reference's rank-0 gates (``multigpu.py:61``) and fixes the
    multi-writer race at ``multinode_torchrun.py:68`` (which gated on
    *local* rank 0, so every node wrote the shared snapshot file).
    """
    return jax.process_index() == 0


def barrier(name: str = "barrier") -> None:
    """Cross-host sync point (used after checkpoint writes so no process races
    ahead and reads a half-written snapshot)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)
