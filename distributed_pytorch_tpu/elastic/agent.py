"""``tpurun`` — the elastic launch agent (torchrun twin).

The reference's rungs 3-4 lean on ``torchrun`` for everything the scripts
don't do themselves (SURVEY.md §3.3): env-var rendezvous, per-node worker
spawning, failure detection, and restart-the-world recovery. This module is
that machinery, TPU-native:

* **Env contract** — workers receive ``COORDINATOR_ADDRESS`` /
  ``NUM_PROCESSES`` / ``PROCESS_ID`` / ``LOCAL_RANK`` /
  ``TPURUN_RESTART_COUNT`` (the torchrun ``MASTER_ADDR:PORT`` / ``WORLD_SIZE``
  / ``RANK`` / ``LOCAL_RANK`` / ``TORCHELASTIC_RESTART_COUNT`` analog,
  reference ``multigpu_torchrun.py:24``); ``setup_distributed()`` consumes
  them (``parallel/bootstrap.py``).
* **Rendezvous** — agents meet at a native C++ TCP store (``elastic/store.py``,
  the c10d TCPStore twin) on the rendezvous host; node 0's agent runs the
  store. Joins are counted per generation; everyone proceeds when all
  ``nnodes`` agents have joined. JAX's own coordination service is a second
  port on the same host, bound by *worker* 0 (``--jax-coordinator-port``,
  default: rendezvous port + 1).
* **Failure detection** — local: the agent polls its workers; any nonzero exit
  is a failure, and with ``--worker-heartbeat-timeout`` a worker that is
  alive but silent (wedged in a collective, SIGSTOPped) is declared hung
  when it stops touching its ``TPURUN_HEARTBEAT_FILE`` (the Trainer touches
  it every batch). Remote: each agent heartbeats ``hb/<node>`` into the store
  and the monitor watches the failure-generation key and peer heartbeats.
* **Recovery** — torchrun's restart-all policy: on any failure the detecting
  agent bumps the generation key; every agent kills its local workers,
  re-rendezvouses at the new generation, and respawns, up to
  ``--max-restarts``. Training survives because the Trainer's snapshot
  contract (probe-on-init, epoch-offset resume — reference
  ``multigpu_torchrun.py:30-40,57-65``) makes workers idempotent.
* **Preemption drain** — SIGTERM on an agent (a maintenance event / spot
  reclaim notice) starts a graceful drain instead of a teardown: the agent
  publishes ``drain/<gen>`` in the store, touches each worker's
  ``TPURUN_DRAIN_FILE`` and soft-signals SIGTERM; the Trainer finishes the
  in-flight step, takes a just-in-time STEP-granular snapshot (all ranks
  agree on the stop step via a per-batch collective, so no survivor ever
  issues a collective against a vanished peer), and exits with
  ``--preempt-exit-code``. The monitor classifies that exit — and any
  drain-marked generation bump — as a *preemption*: the world restarts
  WITHOUT spending ``--max-restarts`` budget, and the reclaimed node's agent
  exits after its workers drain (survivors re-form via MIN:MAX scale-down).
  Workers get ``--drain-grace`` seconds before SIGKILL.
* **Elastic world size** — ``--nnodes MIN:MAX`` (the torchrun elastic form,
  reference launcher surface ``slurm/sbatch_run.sh:17-23``): when a node is
  lost for good, the next rendezvous waits ``--scale-down-grace`` seconds
  for the full world and then re-forms with the >= MIN nodes that joined —
  dense re-ranks, smaller ``NUM_PROCESSES``, loaders re-shard from the new
  env on snapshot resume (every sample still visited exactly once per
  epoch). A node that revives later triggers one restart and scales the
  world back up.

Single node (``--standalone``) and multi-node (``--nnodes``/``--node-rank``/
``--rdzv-endpoint host:port``, the ``sbatch_run.sh:17-23`` shape) use the
identical code path; single-node simply has ``nnodes=1`` and the store on
localhost.

Usage::

    python -m distributed_pytorch_tpu.elastic.agent --nproc-per-node 4 \
        --max-restarts 3 train.py --epochs 10
    # multi-node, on every node:
    python -m distributed_pytorch_tpu.elastic.agent --nnodes 4 --node-rank $I \
        --nproc-per-node 1 --rdzv-endpoint head:29400 train.py
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from distributed_pytorch_tpu.chaos import FaultProxy, get_plan as _get_fault_plan
from distributed_pytorch_tpu.elastic.store import KVStoreClient, KVStoreServer
from distributed_pytorch_tpu.obs import MetricsRegistry
from distributed_pytorch_tpu.utils.platform import (
    local_tpu_chips,
    workers_pinned_to_cpu,
)

GEN_KEY = "tpurun/generation"  # bumped on every failure -> restart-the-world
FATAL_KEY = "tpurun/fatal"  # set when restarts are exhausted or world aborts
DONE_PREFIX = "tpurun/done/"  # done/<gen> counts agents whose workers finished
FINISHED_PREFIX = "tpurun/finished/"  # finished/<gen> terminal marker: done/<gen> reached the world size
# How long the done counter may STALL (no new adds) after a generation bump
# before a locally-succeeded agent honors the bump and restarts: a bump can
# race the last DONE adds (agents add DONE unconditionally once their
# workers succeed, within one ~0.2s poll cycle), so honoring it instantly
# could split the world between "done" and "restart" verdicts. The deadline
# EXTENDS while the counter advances — completion in flight — and a counter
# that stalls (a member truly failed: its DONE will never come) restarts
# after only this long, so genuine failures aren't delayed by a fixed
# worst-case grace.
DONE_BUMP_GRACE = 3.0
ACK_PREFIX = "tpurun/ack/"  # ack/<gen> exit barrier: node 0 keeps the store up until all ack
JOIN_PREFIX = "tpurun/join/"  # join/<gen> counts agents present at <gen>
MEMBER_PREFIX = "tpurun/member/"  # member/<gen>/<orig_rank> -> "1" (who joined)
WORLD_PREFIX = "tpurun/world/"  # world/<gen> -> "0,2,..." settled membership
HB_PREFIX = "tpurun/hb/"  # hb/<node_rank> -> monotonically increasing beat
# drain/<gen> -> "node<rank>": generation <gen> is ending by PREEMPTION, not
# failure. Set by the SIGTERM-caught agent BEFORE it bumps the generation, so
# every peer (a) forwards the soft drain signal to its own workers — the
# in-band drain barrier that stops all ranks at the same step — and (b)
# classifies the coming restart as a preemption (restart budget intact).
DRAIN_PREFIX = "tpurun/drain/"


@dataclass
class ElasticConfig:
    nproc_per_node: int = 1
    nnodes: int = 1
    # torchrun's ``--nnodes MIN:MAX`` lower bound: when a node dies for good,
    # the next rendezvous waits ``scale_down_grace`` seconds for the full
    # world, then re-forms with every node that DID join (>= min_nnodes) —
    # dense re-ranked, workers respawned with the smaller NUM_PROCESSES, and
    # the loader re-sharding from the new env on resume. min_nnodes == nnodes
    # (the default) disables scale-down: rendezvous insists on a full world.
    # Node 0 can never be scaled out — it hosts the store (exactly like
    # torchrun's c10d endpoint host).
    min_nnodes: int = 0  # 0 -> nnodes (fixed-size world)
    scale_down_grace: float = 30.0
    node_rank: int = 0
    rdzv_host: str = "127.0.0.1"
    rdzv_port: int = 29400
    # Port for JAX's own coordination service (run by global process 0 of the
    # *workers*, not by the agent). Defaults to rdzv_port + 1; set explicitly
    # (--jax-coordinator-port) when that neighbor port may be taken.
    jax_coordinator_port: Optional[int] = None
    max_restarts: int = 3
    heartbeat_interval: float = 2.0
    heartbeat_timeout: float = 30.0
    # > 0 enables HUNG-worker detection (exit-code polling only catches death;
    # a worker wedged in a collective whose peer vanished, or SIGSTOPped,
    # would otherwise hang the world silently): each worker gets a
    # TPURUN_HEARTBEAT_FILE env var and must touch that file at least this
    # often once training starts (the Trainer does so every batch). The clock
    # starts at spawn, so set it above worst-case startup + compile time.
    worker_heartbeat_timeout: float = 0.0
    # The blip/dead boundary for the rendezvous store: transport failures are
    # retried transparently inside KVStoreClient for this many seconds (a
    # store restart or network partition shorter than this is INVISIBLE to
    # the agent); only after the deadline does a ConnectionError surface, and
    # the agent then treats the rendezvous host as dead (WorldCompleted /
    # abort, the pre-existing paths).
    store_retry_deadline: float = 30.0
    # Preemption drain: on SIGTERM the agent publishes drain/<gen>, touches
    # each worker's TPURUN_DRAIN_FILE, and soft-signals SIGTERM; workers have
    # this many seconds to finish the in-flight step and snapshot before the
    # group is killed (size it to the platform's reclaim grace minus margin).
    drain_grace: float = 30.0
    # The distinguished exit code a draining worker uses (exported to workers
    # as TPURUN_DRAIN_EXIT_CODE). The monitor classifies this exit as a
    # preemption — restart-the-world WITHOUT decrementing --max-restarts.
    preempt_exit_code: int = 121
    env: Dict[str, str] = field(default_factory=dict)

    @property
    def max_world_size(self) -> int:
        """Worker count of a FULL world. With ``--nnodes MIN:MAX`` the live
        world can be smaller — the per-generation size is always
        ``len(members) * nproc_per_node`` (see :class:`WorkerGroup`)."""
        return self.nnodes * self.nproc_per_node

    @property
    def min_world_nodes(self) -> int:
        return self.min_nnodes or self.nnodes

    @property
    def coordinator_address(self) -> str:
        port = self.jax_coordinator_port
        if port is None:
            port = self.rdzv_port + 1
        return f"{self.rdzv_host}:{port}"


class WorkerGroup:
    """The local workers of one agent: spawn, poll, terminate.

    ``members`` is the settled node membership of this generation (original
    node ranks, sorted): with scale-down it can be smaller than
    ``cfg.nnodes``, and the env contract is computed from the DENSE rank of
    this node within it — workers always see a contiguous, gap-free
    PROCESS_ID space sized to the live world.
    """

    def __init__(
        self,
        cfg: ElasticConfig,
        cmd: List[str],
        restart_count: int,
        members: Optional[List[int]] = None,
    ):
        members = members if members is not None else list(range(cfg.nnodes))
        world_size = len(members) * cfg.nproc_per_node
        dense_rank = members.index(cfg.node_rank)
        self.procs: List[subprocess.Popen] = []
        self.hb_dir: Optional[str] = None
        self.hb_files: List[str] = []
        self.spawned_at = time.monotonic()
        # Per-worker (last observed mtime, monotonic time it changed) — the
        # staleness clock starts at spawn (mtime None until the first touch).
        self._beats: List[tuple] = [
            (None, self.spawned_at) for _ in range(cfg.nproc_per_node)
        ]
        if cfg.worker_heartbeat_timeout > 0:
            import tempfile

            self.hb_dir = tempfile.mkdtemp(prefix="tpurun_hb_")
        # Drain contract: each worker gets a TPURUN_DRAIN_FILE path; the
        # agent touching it (request_drain) is the soft preemption notice the
        # Trainer polls every batch, and TPURUN_DRAIN_EXIT_CODE is the
        # distinguished code a drained worker exits with. The file ALSO
        # disambiguates SIGTERM for the worker: SIGTERM with the file touched
        # means "snapshot and go"; bare SIGTERM (a failure teardown) means
        # "die now" — so failure restarts stay fast.
        import tempfile as _tempfile

        self.drain_dir = _tempfile.mkdtemp(prefix="tpurun_drain_")
        self.drain_files: List[str] = []
        self._drain_sent = False
        for local_rank in range(cfg.nproc_per_node):
            env = dict(os.environ)
            env.update(cfg.env)
            drain_file = os.path.join(self.drain_dir, f"drain_{local_rank}")
            self.drain_files.append(drain_file)
            env.update(
                COORDINATOR_ADDRESS=cfg.coordinator_address,
                NUM_PROCESSES=str(world_size),
                PROCESS_ID=str(dense_rank * cfg.nproc_per_node + local_rank),
                LOCAL_RANK=str(local_rank),
                TPURUN_RESTART_COUNT=str(restart_count),
                TPURUN_DRAIN_FILE=drain_file,
                TPURUN_DRAIN_EXIT_CODE=str(cfg.preempt_exit_code),
            )
            if self.hb_dir is not None:
                hb_file = os.path.join(self.hb_dir, f"hb_{local_rank}")
                env["TPURUN_HEARTBEAT_FILE"] = hb_file
                self.hb_files.append(hb_file)
            self.procs.append(subprocess.Popen(cmd, env=env))

    def request_drain(self) -> None:
        """Deliver the soft preemption notice to every live worker: touch its
        drain file FIRST (so the worker's SIGTERM handler reads this as a
        drain, not a teardown), then SIGTERM. Idempotent."""
        if self._drain_sent:
            return
        self._drain_sent = True
        for drain_file in self.drain_files:
            try:
                with open(drain_file, "w") as f:
                    f.write("drain")
            except OSError:
                pass
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.terminate()  # SIGTERM; the drain file makes it soft
                except OSError:
                    pass

    def hung_worker(self, timeout: float) -> Optional[int]:
        """Local rank of a live worker whose heartbeat file went stale.

        Staleness is judged on THIS process's monotonic clock: we record when
        the observed mtime last *changed* (same pattern as the agent-level
        ``_peer_dead``), so NTP clock steps can neither declare a healthy
        worker hung nor mask a real hang. A worker that never touched its
        file is measured from spawn (startup and first-compile count against
        the timeout — size it accordingly). Finished workers are exempt: no
        more beats are expected of them."""
        now = time.monotonic()
        for local_rank, (proc, hb_file) in enumerate(
            zip(self.procs, self.hb_files)
        ):
            if proc.poll() is not None:
                continue
            try:
                mtime = os.path.getmtime(hb_file)
            except OSError:
                mtime = None
            last_mtime, seen_at = self._beats[local_rank]
            if mtime != last_mtime:
                self._beats[local_rank] = (mtime, now)
            elif now - seen_at > timeout:
                return local_rank
        return None

    def poll(self) -> Optional[int]:
        """None while all run / after all succeeded; first nonzero exit code if
        any worker failed."""
        for p in self.procs:
            code = p.poll()
            if code is not None and code != 0:
                return code
        return None

    def all_done(self) -> bool:
        return all(p.poll() == 0 for p in self.procs)

    def terminate(self, grace: float = 10.0) -> None:
        """SIGTERM every live worker, then ESCALATE to SIGKILL for any still
        alive past the shared ``grace`` deadline. The escalation is
        load-bearing: a worker mid-drain-snapshot (or wedged inside one, or
        one that installed a SIGTERM handler and got stuck) must not block
        agent teardown forever. SIGKILL cannot be caught, so the post-kill
        ``wait`` always returns — but it is still bounded defensively (a
        zombie reparented by a dying init, an uninterruptible-D-state worker)
        rather than allowed to wedge the whole restart loop."""
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + grace
        for p in self.procs:
            timeout = max(0.0, deadline - time.monotonic())
            try:
                p.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                try:
                    p.kill()
                except OSError:
                    pass
                try:
                    p.wait(timeout=10.0)
                except subprocess.TimeoutExpired:
                    pass  # unreapable (kernel-stuck); do not block teardown
        import shutil

        if self.hb_dir is not None:
            shutil.rmtree(self.hb_dir, ignore_errors=True)
            self.hb_dir = None
        if self.drain_dir is not None:
            shutil.rmtree(self.drain_dir, ignore_errors=True)
            self.drain_dir = None


class _Retry(Exception):
    """Internal: loop the rendezvous again; carries the grace clock, which
    resets when a NEW generation was joined during the pass."""

    def __init__(self, grace_start: float):
        self.grace_start = grace_start


class WorldCompleted(Exception):
    """The world finished without this node: either the rendezvous store
    vanished mid-rendezvous (it lives on node 0's agent, which tears it
    down only when the world finished), or — ``finished=True`` — the store
    is still up and the settled generation's done counter already reached
    its member count. A node still trying to (re)join (e.g. one revived
    after a scale-down) should exit cleanly, not crash or force a restart
    of a world that has nothing left to restart."""

    def __init__(self, finished: bool = False):
        super().__init__()
        self.finished = finished


def _refuse_shared_tpu(cfg: ElasticConfig) -> None:
    """Several workers on one TPU host cannot work today: workers are not
    assigned chips, so each asks libtpu for every local chip, the first to
    start takes them all and the rest die with "The TPU is already in use"
    — which the agent would then restart ``max_restarts`` times. Refuse
    before anything is spawned. One worker per host drives all of its
    chips; workers pinned to the CPU are unaffected."""
    if cfg.nproc_per_node <= 1:
        return
    env = {**os.environ, **cfg.env}
    chips = local_tpu_chips()
    if chips and not workers_pinned_to_cpu(env):
        raise SystemExit(
            f"tpurun: --nproc-per-node {cfg.nproc_per_node} on a host with "
            f"{chips} TPU chip(s): a chip belongs to one process and "
            "workers are not assigned chips of their own, so every worker "
            "after the first would fail to open the TPU. Use "
            "--nproc-per-node 1 (one process drives all local chips), or "
            "set JAX_PLATFORMS=cpu for a CPU run."
        )


class ElasticAgent:
    """One per node. Runs the rendezvous/spawn/monitor/restart loop."""

    def __init__(self, cfg: ElasticConfig, cmd: List[str]):
        _refuse_shared_tpu(cfg)
        self.cfg = cfg
        self.cmd = cmd
        self.server: Optional[KVStoreServer] = None
        if cfg.node_rank == 0:
            self.server = KVStoreServer(cfg.rdzv_port)
        # Chaos: when the armed FaultPlan carries store_partition faults,
        # route this agent's store traffic through a local FaultProxy so the
        # partition can be injected without touching the real store. The
        # server (above) still binds the real rdzv port for the other agents.
        self._chaos_proxy: Optional[FaultProxy] = None
        store_host, store_port = cfg.rdzv_host, cfg.rdzv_port
        plan = _get_fault_plan()
        if plan is not None and plan.store_partitions():
            self._chaos_proxy = FaultProxy(cfg.rdzv_host, cfg.rdzv_port).start()
            self._chaos_proxy.apply_plan(plan)
            store_host, store_port = self._chaos_proxy.host, self._chaos_proxy.port
            print(
                f"[tpurun] chaos: store traffic via FaultProxy "
                f"{store_host}:{store_port}",
                flush=True,
            )
        self._store_endpoint = (store_host, store_port)
        self.store = KVStoreClient(
            store_host, store_port, retry_deadline=cfg.store_retry_deadline
        )
        self._stop_hb = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        self._group: Optional[WorkerGroup] = None
        self._joined_generations: set = set()
        # rank -> (last beat value, local monotonic time it changed)
        self._peer_beats: Dict[int, tuple] = {}
        # Set by the SIGTERM handler (main()): THIS node is being reclaimed.
        # Signal handlers must not touch the store client (the main thread
        # may be mid-request on the same socket), so the handler only sets
        # the event; the monitor loop performs the store publish + worker
        # drain on its next 0.2s pass.
        self._drain_requested = threading.Event()
        # Unified observability: restart/drain/chaos counters in an
        # ``elastic_``-namespaced registry (push-style Counters — the run
        # loop's locals keep their budget semantics, these are the export
        # surface). Incremented alongside, never instead.
        self.registry = MetricsRegistry(namespace="elastic")
        self._c_spawns = self.registry.counter("spawns_total")
        self._c_restarts = self.registry.counter("restarts_total")
        self._c_preempt_restarts = self.registry.counter(
            "preempt_restarts_total"
        )
        self._c_drains = self.registry.counter("drains_requested_total")
        # Goodput accounting, agent half: wall-clock lost to respawn
        # churn — from the moment a generation's failure is classified to
        # the next spawn — split by whether the restart was a free
        # preemption or burned the failure budget.
        self._c_restart_downtime = self.registry.counter(
            "restart_downtime_seconds_total",
            help="Wall-clock between failure classification and respawn",
        )
        self._c_preempt_downtime = self.registry.counter(
            "preempt_downtime_seconds_total",
            help="Restart downtime attributable to preemption drains",
        )
        self.registry.gauge(
            "chaos_faults_armed",
            float(len(plan.faults)) if plan is not None else 0.0,
        )

    def request_drain(self) -> bool:
        """Begin a graceful preemption drain (signal-handler safe: flag only).
        Returns False if a drain was already in progress — the caller should
        then escalate to an immediate exit (second SIGTERM = die now)."""
        if self._drain_requested.is_set():
            return False
        self._drain_requested.set()
        self._c_drains.inc()
        return True

    # ------------------------------------------------------------- heartbeat
    def _heartbeat_loop(self) -> None:
        """Publish a monotonically increasing beat counter on one persistent
        connection; reconnect on transient store errors (a dropped beat must
        not look like a dead node)."""
        beat = 0
        client: Optional[KVStoreClient] = None
        while not self._stop_hb.wait(self.cfg.heartbeat_interval):
            beat += 1
            try:
                if client is None:
                    # retry_deadline=0: a beat is time-sensitive — better to
                    # drop it and reconnect next interval than block the
                    # loop retrying (this loop IS the liveness signal).
                    client = KVStoreClient(
                        *self._store_endpoint,
                        connect_timeout=5.0,
                        retry_deadline=0.0,
                    )
                client.set(f"{HB_PREFIX}{self.cfg.node_rank}", str(beat))
            except (ConnectionError, OSError):
                if client is not None:
                    client.close()
                client = None  # retry with a fresh connection next beat
        if client is not None:
            client.close()

    def _peer_dead(self, members: Optional[List[int]] = None) -> Optional[int]:
        """Node rank of a peer whose heartbeat went stale, if any.

        Only peers in ``members`` (this generation's settled world) are
        consulted: after a scale-down, the long-dead node's stale beat must
        not re-trigger a restart every generation.

        Staleness is judged purely on this node's monotonic clock — the beat
        value is an opaque counter, never a timestamp — so cross-host clock
        skew cannot declare a healthy peer dead. A peer with no beat at all is
        measured from when monitoring began (``_seed_peer_clocks``): every
        agent rendezvoused before workers spawned, so a node frozen before its
        first heartbeat write must still be declared dead, not waited on
        forever."""
        now = time.monotonic()
        ranks = members if members is not None else range(self.cfg.nnodes)
        for rank in ranks:
            if rank == self.cfg.node_rank:
                continue
            beat = self.store.get(f"{HB_PREFIX}{rank}")
            last_beat, seen_at = self._peer_beats.get(rank, (None, None))
            if seen_at is None or beat != last_beat:
                self._peer_beats[rank] = (beat, now)
            elif now - seen_at > self.cfg.heartbeat_timeout:
                return rank
        return None

    def _seed_peer_clocks(self, members: Optional[List[int]] = None) -> None:
        """(Re)start every peer's staleness clock at monitor start.

        Each generation grants each peer a fresh ``heartbeat_timeout`` window:
        a peer declared dead last generation has just re-rendezvoused, and its
        pre-freeze beat value must not count as already-stale (that would
        re-declare a recovered node dead instantly and burn extra restarts)."""
        now = time.monotonic()
        ranks = members if members is not None else range(self.cfg.nnodes)
        for rank in ranks:
            if rank != self.cfg.node_rank:
                last_beat = self._peer_beats.get(rank, (None, None))[0]
                self._peer_beats[rank] = (last_beat, now)

    # ------------------------------------------------------------- lifecycle
    def _rendezvous(self, timeout: float = 600.0) -> tuple:
        """Join the current generation and block until the world settles;
        returns ``(generation, members)`` where ``members`` is the sorted
        list of original node ranks in this generation's world.

        Concurrent failures can bump the generation while we wait (two
        agents may each bump for the same incident — ADD is atomic, so the
        world just skips a number); re-join whatever the latest generation
        is, joining each at most once so counts stay exact.

        Membership is decided by ONE writer — node 0, which hosts the store
        and is therefore always present — and published under
        ``world/<gen>``, so every agent sees the identical member list with
        no read races. Node 0 publishes the moment all ``nnodes`` agents
        join; with ``min_nnodes < nnodes`` (torchrun's ``MIN:MAX``) it also
        publishes after ``scale_down_grace`` seconds once at least
        ``min_nnodes`` joined — the scale-down path. An agent that joins
        AFTER the world settled without it (a node revived past the grace
        window) bumps the generation, forcing a fresh rendezvous that
        includes it — torchrun's join-triggers-restart, which is also the
        scale-UP path. While a world is degraded, every later rendezvous
        pays the grace wait for the missing nodes before re-settling small;
        keep ``scale_down_grace`` modest.
        """
        cfg = self.cfg
        deadline = time.monotonic() + timeout
        grace_start = time.monotonic()
        while time.monotonic() < deadline:
            try:
                return self._rendezvous_once(cfg, grace_start)
            except _Retry as r:
                grace_start = r.grace_start
            except (ConnectionError, OSError):
                # Store gone: node 0's agent tears it down only after the
                # world completed. A node still (re)joining — e.g. revived
                # after a scale-down — should exit cleanly, not crash.
                raise WorldCompleted() from None
        raise RuntimeError(
            f"rendezvous timed out ({self.cfg.nnodes} nodes expected)"
        )

    def _rendezvous_once(self, cfg, grace_start):
        """One pass of the join/settle/read protocol; raises ``_Retry`` to
        loop (carrying the possibly-reset grace clock)."""
        generation = int(self.store.get(GEN_KEY) or 0)
        if generation not in self._joined_generations:
            # Membership mark BEFORE the join count: when the counter
            # reads n, all n member keys are already visible.
            self.store.set(f"{MEMBER_PREFIX}{generation}/{cfg.node_rank}", "1")
            self.store.add(f"{JOIN_PREFIX}{generation}", 1)
            self._joined_generations.add(generation)
            grace_start = time.monotonic()
        world = self.store.get(f"{WORLD_PREFIX}{generation}")
        if world is None:
            joined = self.store.wait_ge(
                f"{JOIN_PREFIX}{generation}", cfg.nnodes, timeout=2.0
            )
            if int(self.store.get(GEN_KEY) or 0) != generation:
                raise _Retry(grace_start)  # bumped: rejoin at the new gen
            if cfg.node_rank != 0:
                raise _Retry(grace_start)  # await node 0's decision
            present = sorted(
                r
                for r in range(cfg.nnodes)
                if self.store.get(f"{MEMBER_PREFIX}{generation}/{r}")
            )
            if joined is not None or (
                time.monotonic() - grace_start > cfg.scale_down_grace
                and len(present) >= cfg.min_world_nodes
            ):
                if joined is None:
                    print(
                        f"[tpurun] scale-down: only {len(present)}/"
                        f"{cfg.nnodes} node(s) joined gen {generation} "
                        f"within {cfg.scale_down_grace:.0f}s grace; "
                        f"re-forming with nodes {present}",
                        flush=True,
                    )
                self.store.set(
                    f"{WORLD_PREFIX}{generation}",
                    ",".join(str(r) for r in present),
                )
            raise _Retry(grace_start)
        members = [int(r) for r in world.split(",")]
        if cfg.node_rank not in members:
            # The world settled without us (we are a revived latecomer).
            # If that world has ALREADY completed (every member reported
            # done), bumping the generation would split the finishing
            # agents between exit-0 and restart-into-a-dead-store (ADVICE
            # r04) — and there is nothing left to restart anyway.
            done = int(self.store.get(f"{DONE_PREFIX}{generation}") or 0)
            if done >= len(members) or self.store.get(
                f"{FINISHED_PREFIX}{generation}"
            ):
                raise WorldCompleted(finished=True)
            # Otherwise force a fresh generation that includes everyone.
            self.store.add(GEN_KEY, 1)
            raise _Retry(grace_start)
        return generation, members

    def run(self) -> int:
        cfg = self.cfg
        self._hb_thread = threading.Thread(target=self._heartbeat_loop, daemon=True)
        self._hb_thread.start()
        # Two counters, deliberately separate: ``spawns`` feeds the workers'
        # TPURUN_RESTART_COUNT (the spawn GENERATION — chaos plans and any
        # restart-keyed worker logic must see it advance on every respawn,
        # free or not), while ``restarts`` is the --max-restarts BUDGET and
        # only advances on real failures — a preemption drain restarts the
        # world for free.
        spawns = 0
        restarts = 0
        # (monotonic time failure was classified, was it a preemption) —
        # closed when the replacement WorkerGroup spawns, so the downtime
        # counters cover terminate + re-rendezvous, the full gap.
        fail_at: Optional[tuple] = None
        try:
            while True:
                try:
                    generation, members = self._rendezvous()
                except WorldCompleted as wc:
                    if wc.finished:
                        # The settled world (which excludes us) has fully
                        # completed — the job succeeded without this node.
                        # Clean exit, whether or not we ran workers in an
                        # earlier generation.
                        print(
                            "[tpurun] world completed without this "
                            "(excluded) node; exiting",
                            flush=True,
                        )
                        return 0
                    if self._group is None:
                        # Never spawned workers in this process: we are a
                        # revived latecomer and the world finished without
                        # us — a clean no-op exit.
                        print(
                            "[tpurun] rendezvous store gone — the world "
                            "completed without this (revived) node; exiting",
                            flush=True,
                        )
                        return 0
                    # We WERE part of this world (workers ran and the job
                    # is unfinished, or we'd have exited via the done
                    # barrier): losing the store mid-run means node 0 died.
                    # That is a failure, never silent success.
                    print(
                        "[tpurun] rendezvous store lost mid-run (node 0 "
                        "dead?); aborting",
                        file=sys.stderr,
                    )
                    return 1
                if cfg.node_rank == 0:
                    print(
                        f"[tpurun] generation {generation}: {len(members)} "
                        f"node(s) x {cfg.nproc_per_node} proc(s), "
                        f"world={len(members) * cfg.nproc_per_node}",
                        flush=True,
                    )
                group = self._group = WorkerGroup(
                    cfg, self.cmd, spawns, members=members
                )
                if fail_at is not None:
                    downtime = time.monotonic() - fail_at[0]
                    self._c_restart_downtime.inc(downtime)
                    if fail_at[1]:
                        self._c_preempt_downtime.inc(downtime)
                    fail_at = None
                failure = self._monitor(group, generation, members)
                if failure is None:
                    # Local workers all succeeded; wait for every live agent.
                    done_count = self.store.add(f"{DONE_PREFIX}{generation}", 1)
                    if done_count is not None and int(done_count) >= len(members):
                        # We are the DECIDER (our add completed the count):
                        # publish the terminal marker so agents that later
                        # observe a stray generation bump (revived-latecomer
                        # race, ADVICE r04) still agree the world finished.
                        self.store.set(f"{FINISHED_PREFIX}{generation}", "1")
                    result = self._await_world_done(generation, len(members))
                    if result == "done":
                        # Exit barrier: the store lives on node 0, so node 0
                        # must not tear it down until every agent has seen
                        # "done" (else their final waits die mid-request).
                        try:
                            self.store.add(f"{ACK_PREFIX}{generation}", 1)
                            if self.cfg.node_rank == 0:
                                self.store.wait_ge(
                                    f"{ACK_PREFIX}{generation}",
                                    len(members),
                                    timeout=60.0,
                                )
                        except (ConnectionError, OSError):
                            pass  # store already gone -> world is done anyway
                        return 0
                    # else: someone failed after we finished -> fall through to restart
                    failure = "restart requested elsewhere"
                # Classify BEFORE terminate: a drain-marked generation ended
                # by preemption, not failure, however this agent noticed it
                # (drain exit code, generation bump, or its own SIGTERM).
                preempt = failure.startswith("preempt")
                if not preempt:
                    try:
                        preempt = bool(
                            self.store.get(f"{DRAIN_PREFIX}{generation}")
                        )
                    except (ConnectionError, OSError):
                        pass
                group.terminate()
                if self.store.get(FATAL_KEY):
                    print("[tpurun] aborting: world marked fatal", file=sys.stderr)
                    return 1
                if self._drain_requested.is_set():
                    # THIS node is the one being reclaimed: workers drained
                    # (or were reaped at the grace deadline) — exit instead
                    # of respawning, so the survivors can re-form without us
                    # (the MIN:MAX scale-down path).
                    print(
                        "[tpurun] drain complete; exiting (node preempted)",
                        flush=True,
                    )
                    return 143  # 128 + SIGTERM: conventional reclaim exit
                spawns += 1
                self._c_spawns.inc()
                fail_at = (time.monotonic(), preempt)
                if preempt:
                    self._c_preempt_restarts.inc()
                    print(
                        f"[tpurun] preempt detected (gen {generation}): "
                        f"{failure}; restart budget intact "
                        f"({restarts}/{cfg.max_restarts} used)",
                        flush=True,
                    )
                    continue
                restarts += 1
                self._c_restarts.inc()
                if restarts > cfg.max_restarts:
                    self.store.set(FATAL_KEY, f"node{cfg.node_rank}-restarts-exhausted")
                    print(
                        f"[tpurun] giving up after {cfg.max_restarts} restarts",
                        file=sys.stderr,
                    )
                    return 1
                print(
                    f"[tpurun] failure detected (gen {generation}): "
                    f"{failure}; restart {restarts}/{cfg.max_restarts}",
                    flush=True,
                )
        finally:
            self._stop_hb.set()
            self.close()

    def _monitor(
        self,
        group: WorkerGroup,
        generation: int,
        members: Optional[List[int]] = None,
    ) -> Optional[str]:
        """Poll local workers + the store until success (None) or failure (str).

        On local failure, bumps the generation so every other agent restarts
        too (torchrun's restart-the-world semantics).

        Preemption drain: a SIGTERM on THIS agent (``_drain_requested``) or a
        ``drain/<gen>`` mark from a preempted peer starts a drain — the soft
        notice is forwarded to the local workers (``request_drain``), which
        finish the in-flight step, snapshot, and exit with the distinguished
        drain code within ``--drain-grace``. A drain exit returns a
        ``"preempt: ..."`` failure string so ``run()`` restarts the world
        WITHOUT spending budget; the drain mark is published before the
        generation bump so peers classify identically.
        """
        cfg = self.cfg
        last_peer_check = 0.0
        n_peers = len(members) if members is not None else cfg.nnodes
        self._seed_peer_clocks(members)
        drain_key = f"{DRAIN_PREFIX}{generation}"
        drain_signaled = False
        drain_deadline: Optional[float] = None
        while True:
            code = group.poll()
            if code is not None:
                if code == cfg.preempt_exit_code:
                    # Drain exit: publish the mark BEFORE the bump so every
                    # peer sees "preemption", then restart-the-world.
                    self.store.set(drain_key, f"node{cfg.node_rank}")
                    self.store.add(GEN_KEY, 1)
                    return f"preempt: local worker drained (exit {code})"
                self.store.add(GEN_KEY, 1)
                return f"local worker exited with {code}"
            if group.all_done():
                return None
            if self._drain_requested.is_set() and not drain_signaled:
                # This node is being reclaimed: publish, then soft-signal.
                drain_signaled = True
                drain_deadline = time.monotonic() + cfg.drain_grace
                print(
                    f"[tpurun] drain: SIGTERM received; workers have "
                    f"{cfg.drain_grace:.0f}s to snapshot and exit",
                    flush=True,
                )
                self.store.set(drain_key, f"node{cfg.node_rank}")
                group.request_drain()
            if not drain_signaled and self.store.get(drain_key):
                # A peer is being reclaimed: forward the soft notice so our
                # ranks join the drain at the same step (the Trainer's
                # per-batch allgather agreement) instead of later issuing a
                # collective against the vanished peer.
                drain_signaled = True
                drain_deadline = time.monotonic() + cfg.drain_grace
                print(
                    f"[tpurun] drain: peer preemption published for gen "
                    f"{generation}; draining local workers",
                    flush=True,
                )
                group.request_drain()
            if drain_deadline is not None and time.monotonic() > drain_deadline:
                # Wedged mid-drain (e.g. stuck in a snapshot barrier against
                # a peer already gone): reap and still classify as preempt —
                # the node WAS preempted; the resume falls back to the last
                # durable snapshot.
                self.store.add(GEN_KEY, 1)
                return "preempt: drain grace expired (workers killed)"
            current_gen = int(self.store.get(GEN_KEY) or 0)
            if current_gen != generation:
                if self.store.get(drain_key):
                    # The preempted node finished draining and bumped; our
                    # workers are mid-drain — keep monitoring (bounded by
                    # drain_deadline) so their just-in-time snapshot lands
                    # instead of being torn apart by an instant teardown.
                    if not drain_signaled:
                        drain_signaled = True
                        drain_deadline = time.monotonic() + cfg.drain_grace
                        group.request_drain()
                else:
                    return "remote failure (generation bumped)"
            if self.store.get(FATAL_KEY):
                return "fatal"
            now = time.monotonic()
            if n_peers > 1 and now - last_peer_check > cfg.heartbeat_interval:
                last_peer_check = now
                dead = self._peer_dead(members)
                if dead is not None:
                    self.store.add(GEN_KEY, 1)
                    return f"node {dead} heartbeat lost"
            if cfg.worker_heartbeat_timeout > 0:
                hung = group.hung_worker(cfg.worker_heartbeat_timeout)
                if hung is not None:
                    self.store.add(GEN_KEY, 1)
                    return f"local worker {hung} hung (heartbeat file stale)"
            time.sleep(0.2)

    def _await_world_done(self, generation: int, n_members: int) -> str:
        """After local success: block until all live agents report done
        ('done') or a failure elsewhere bumps the generation ('restart').

        A generation bump is NOT immediately terminal: members add DONE
        unconditionally once their workers succeed (their monitor checks
        completion before the bump flag), so a bump can race the last DONE
        adds — e.g. a revived latecomer bumping while the world finishes
        (ADVICE r04). On seeing a bump, keep waiting only while the counter
        is still ADVANCING (completion in flight); once it stalls for
        ``DONE_BUMP_GRACE`` the missing member has truly failed — restart.
        FATAL is honored immediately (the counter cannot save a world whose
        restart budget is spent)."""
        last_done = -1
        stall_deadline = None
        while True:
            try:
                done = self.store.wait_ge(
                    f"{DONE_PREFIX}{generation}", n_members, timeout=1.0
                )
                if done is not None or self.store.get(
                    f"{FINISHED_PREFIX}{generation}"
                ):
                    return "done"
                if self.store.get(FATAL_KEY):
                    return "restart"
                if int(self.store.get(GEN_KEY) or 0) != generation:
                    done_now = int(
                        self.store.get(f"{DONE_PREFIX}{generation}") or 0
                    )
                    now = time.monotonic()
                    if done_now != last_done:
                        last_done = done_now
                        stall_deadline = now + DONE_BUMP_GRACE
                    elif now > stall_deadline:
                        return "restart"
                else:
                    last_done = -1
                    stall_deadline = None
            except (ConnectionError, OSError):
                # The store dies only when node 0's agent exits — and after our
                # own workers succeeded that means the world completed.
                return "done"

    def close(self) -> None:
        self._stop_hb.set()
        if self._group is not None:
            self._group.terminate()
            self._group = None
        try:
            if self.server is not None:
                self.store.shutdown_server()
        finally:
            self.store.close()
            if self.server is not None:
                self.server.close()
            if self._chaos_proxy is not None:
                self._chaos_proxy.stop()
                self._chaos_proxy = None


def _parse_endpoint(endpoint: str) -> tuple:
    host, _, port = endpoint.rpartition(":")
    return host or "127.0.0.1", int(port)


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tpurun",
        description="Elastic launcher for distributed_pytorch_tpu (torchrun twin)",
    )
    p.add_argument("--nproc-per-node", type=int, default=1)
    p.add_argument(
        "--nnodes",
        default="1",
        help="node count N, or MIN:MAX (torchrun elastic form): start with "
        "up to MAX nodes and allow the world to re-form with as few as MIN "
        "when nodes are lost for good (--scale-down-grace)",
    )
    p.add_argument("--node-rank", type=int, default=0)
    p.add_argument(
        "--scale-down-grace",
        type=float,
        default=30.0,
        help="with --nnodes MIN:MAX, how long each rendezvous waits for the "
        "full MAX world before settling for the >= MIN nodes that joined",
    )
    p.add_argument(
        "--rdzv-endpoint",
        default="127.0.0.1:29400",
        help="host:port of the rendezvous store (runs on node 0)",
    )
    p.add_argument("--max-restarts", type=int, default=3)
    p.add_argument(
        "--jax-coordinator-port",
        type=int,
        default=None,
        help="port for jax.distributed's coordination service, which worker 0 "
        "binds on the rendezvous host (default: rendezvous port + 1)",
    )
    p.add_argument(
        "--heartbeat-interval",
        type=float,
        default=2.0,
        help="seconds between agent heartbeats into the store",
    )
    p.add_argument(
        "--heartbeat-timeout",
        type=float,
        default=30.0,
        help="declare a peer node dead after this many seconds without a "
        "fresh heartbeat (restart-the-world follows)",
    )
    p.add_argument(
        "--worker-heartbeat-timeout",
        type=float,
        default=0.0,
        help="> 0: declare a LOCAL worker hung (and restart the world) when "
        "it has not touched its TPURUN_HEARTBEAT_FILE for this many seconds "
        "(the Trainer touches it every batch); the clock starts at spawn, so "
        "allow for startup + first compile",
    )
    p.add_argument(
        "--store-retry-deadline",
        type=float,
        default=30.0,
        help="seconds the store client transparently retries a transport "
        "failure (reconnect + backoff) before the agent concludes the "
        "rendezvous host is dead; 0 disables retry (fail fast)",
    )
    p.add_argument(
        "--drain-grace",
        type=float,
        default=30.0,
        help="seconds workers get to finish the in-flight step and take a "
        "just-in-time snapshot after a preemption SIGTERM, before the group "
        "is killed (size to the platform's reclaim grace minus a margin)",
    )
    p.add_argument(
        "--preempt-exit-code",
        type=int,
        default=121,
        help="the distinguished exit code of a gracefully drained worker "
        "(exported as TPURUN_DRAIN_EXIT_CODE); the agent classifies it as a "
        "preemption and restarts the world WITHOUT spending --max-restarts "
        "budget",
    )
    p.add_argument(
        "--standalone",
        action="store_true",
        help="single-node shorthand: nnodes=1, store on an ephemeral local port",
    )
    p.add_argument("script", help="training script to launch")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p


def _free_ports(n: int) -> List[int]:
    """``n`` distinct free ports, all held open while picking so two calls
    cannot hand back the same just-released port."""
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _parse_nnodes(spec: str) -> tuple:
    """``"4"`` -> (4, 4); ``"1:4"`` -> (1, 4) (torchrun MIN:MAX)."""
    s = str(spec)
    if ":" in s:
        lo, hi = s.split(":", 1)
        lo, hi = int(lo), int(hi)
    else:
        lo = hi = int(s)
    if not (1 <= lo <= hi):
        raise ValueError(f"invalid --nnodes {spec!r}: need 1 <= MIN <= MAX")
    return lo, hi


def main(argv: Optional[List[str]] = None) -> int:
    args = make_parser().parse_args(argv)
    min_nnodes, max_nnodes = _parse_nnodes(args.nnodes)
    args.nnodes = max_nnodes
    if args.standalone:
        args.nnodes, args.node_rank, min_nnodes = 1, 0, 1
        # The ephemeral store port's neighbor may be in use; pick two distinct
        # free ports rather than gambling on rdzv_port + 1.
        rdzv_port, coord_port = _free_ports(2)
        args.rdzv_endpoint = f"127.0.0.1:{rdzv_port}"
        if args.jax_coordinator_port is None:
            args.jax_coordinator_port = coord_port
    host, port = _parse_endpoint(args.rdzv_endpoint)
    cfg = ElasticConfig(
        nproc_per_node=args.nproc_per_node,
        nnodes=args.nnodes,
        min_nnodes=min_nnodes,
        scale_down_grace=args.scale_down_grace,
        node_rank=args.node_rank,
        rdzv_host=host,
        rdzv_port=port,
        jax_coordinator_port=args.jax_coordinator_port,
        max_restarts=args.max_restarts,
        heartbeat_interval=args.heartbeat_interval,
        heartbeat_timeout=args.heartbeat_timeout,
        worker_heartbeat_timeout=args.worker_heartbeat_timeout,
        store_retry_deadline=args.store_retry_deadline,
        drain_grace=args.drain_grace,
        preempt_exit_code=args.preempt_exit_code,
    )
    agent = ElasticAgent(cfg, [sys.executable, args.script] + args.script_args)

    def _forward_signal(signum, frame):
        agent.close()
        sys.exit(128 + signum)

    def _graceful_drain(signum, frame):
        # First SIGTERM: begin the preemption drain (publish + soft-signal
        # happen on the monitor thread — a signal handler must not touch the
        # store socket the main thread may be mid-request on). A SECOND
        # SIGTERM escalates to the immediate teardown, exactly the pre-drain
        # behavior (and what a reclaim's follow-up SIGKILL would force anyway).
        if not agent.request_drain():
            _forward_signal(signum, frame)

    signal.signal(signal.SIGTERM, _graceful_drain)
    signal.signal(signal.SIGINT, _forward_signal)
    return agent.run()


if __name__ == "__main__":
    sys.exit(main())
