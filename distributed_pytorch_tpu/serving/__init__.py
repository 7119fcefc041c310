"""Continuous-batching inference engine — the serving side of the LM family.

``generation.py`` is strictly offline: one fixed batch in, one compiled
``fori_loop`` out, and no request may join until every sequence in the batch
finishes. This package turns the same decode math into a REQUEST-level
engine, split exactly along the pjit paper's host/device line:

* the device runs ONE fixed-shape jit decode step (padded slots masked out,
  so there is exactly one compilation per shape bucket);
* the host owns everything irregular: the refcounted paged KV allocator and
  the prefix-cache trie (:mod:`.kv_cache`), the waiting queue /
  chunked-prefill / copy-on-write / preemption policy (:mod:`.scheduler`),
  and admission control + latency metrics (:mod:`.admission`);
* :class:`.engine.InferenceEngine` glues them behind
  ``submit(prompt, params) -> request_id`` / ``step()`` / ``poll()``.

Deterministic on CPU (``JAX_PLATFORMS=cpu``): tests assert continuous
batching reproduces offline ``generate()`` token for token.
"""

from distributed_pytorch_tpu.serving.admission import (
    AdmissionController,
    AdmissionError,
    EngineDraining,
    QueueFull,
    RequestTooLong,
    ServingMetrics,
)
from distributed_pytorch_tpu.serving.elastic import (
    DrainController,
    EngineSnapshot,
    RequestSnapshot,
    SnapshotUnavailable,
    adopt_snapshot,
    drain_engine,
    publish_snapshot,
    restore_engine,
    snapshot_engine,
)
from distributed_pytorch_tpu.serving.engine import InferenceEngine
from distributed_pytorch_tpu.serving.fleet import (
    AutoscalePolicy,
    FleetRouter,
    NoLiveReplica,
    prefix_affinity_key,
)
from distributed_pytorch_tpu.serving.frontdoor import (
    FrontDoor,
    TenantConfig,
    TenantQuotaExceeded,
    TokenStream,
)
from distributed_pytorch_tpu.serving.grammar import (
    TokenDFA,
    compile_grammar,
)
from distributed_pytorch_tpu.serving.hostkv import HostPageTier
from distributed_pytorch_tpu.serving.journal import (
    Journal,
    JournalError,
    JournalState,
    pid_alive,
    read_worker_registry,
    replay_journal,
)
from distributed_pytorch_tpu.serving.mods import (
    AdapterStore,
    Mods,
    ModState,
)
from distributed_pytorch_tpu.serving.kv_cache import (
    BlockTable,
    OutOfPages,
    PagePoolGroup,
    PagedBlockAllocator,
    PrefixCache,
    WindowGroup,
    WindowTable,
)
from distributed_pytorch_tpu.serving.mesh import (
    make_serving_mesh,
    mesh_fingerprint,
)
from distributed_pytorch_tpu.serving.replica import (
    CircuitBreaker,
    LocalReplicaClient,
    ProcessReplicaClient,
    ReplicaClient,
    ReplicaDead,
    ReplicaError,
    ReplicaUnavailable,
    spawn_replica_clients,
)
from distributed_pytorch_tpu.serving.scheduler import (
    PENDING_TOKEN,
    Request,
    RequestState,
    SamplingParams,
    Scheduler,
    StepPlan,
)

__all__ = [
    "AdapterStore",
    "AdmissionController",
    "AdmissionError",
    "AutoscalePolicy",
    "BlockTable",
    "CircuitBreaker",
    "DrainController",
    "EngineDraining",
    "EngineSnapshot",
    "FleetRouter",
    "FrontDoor",
    "HostPageTier",
    "InferenceEngine",
    "Journal",
    "JournalError",
    "JournalState",
    "LocalReplicaClient",
    "ModState",
    "Mods",
    "NoLiveReplica",
    "OutOfPages",
    "PENDING_TOKEN",
    "PagePoolGroup",
    "PagedBlockAllocator",
    "PrefixCache",
    "ProcessReplicaClient",
    "QueueFull",
    "ReplicaClient",
    "ReplicaDead",
    "ReplicaError",
    "ReplicaUnavailable",
    "Request",
    "RequestSnapshot",
    "RequestState",
    "RequestTooLong",
    "SamplingParams",
    "Scheduler",
    "ServingMetrics",
    "SnapshotUnavailable",
    "StepPlan",
    "TenantConfig",
    "TenantQuotaExceeded",
    "TokenDFA",
    "TokenStream",
    "WindowGroup",
    "WindowTable",
    "adopt_snapshot",
    "compile_grammar",
    "drain_engine",
    "make_serving_mesh",
    "mesh_fingerprint",
    "pid_alive",
    "prefix_affinity_key",
    "publish_snapshot",
    "read_worker_registry",
    "replay_journal",
    "restore_engine",
    "snapshot_engine",
    "spawn_replica_clients",
]
