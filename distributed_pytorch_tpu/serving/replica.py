"""Replica clients: the FleetRouter's handle on one replica, local or not.

Until now every replica the router drove was an ``InferenceEngine`` object
in the router's own process, so "replica death" could only ever be an
analogy — an abandoned Python object, not a vanished interpreter. This
module splits the handle from the engine behind a small interface:

* :class:`LocalReplicaClient` wraps an in-process engine. Every method is
  a direct delegate; behavior is byte-identical to the pre-refactor
  router (``tests/test_serving_fleet.py`` runs unmodified against it).
* :class:`ProcessReplicaClient` drives a replica WORKER SUBPROCESS
  (``serving/replica_worker.py``: engine + IntrospectionServer + a
  stdlib-HTTP control endpoint), spawned with the same env/handshake/
  terminate-with-grace idioms as the elastic agent's WorkerGroup. The
  child can genuinely die (SIGKILL), hang (SIGSTOP), or fall off the
  network (black-holed socket) — and the client is built to survive all
  three.

The robustness layer is the point, not a footnote:

* every control-plane call has a per-call deadline;
* idempotent calls (submit — deduped by a client-minted request id the
  worker keeps a replay map for, exactly like the KV store's
  ``(client_id, seq)`` replay map — cancel, poll, health) get bounded
  jittered-exponential retries; ``step`` is NOT retried (a landed step
  advances decode state, so replaying it is not a retry but a second
  step) — instead its results are delivered at-least-once via an ack
  protocol (the worker re-reports finished ids until the client acks
  them on its next step call);
* a per-replica :class:`CircuitBreaker` opens after K consecutive
  transport failures, fast-fails every call while open, and lets exactly
  one probe through per cooldown (half-open) — so a hung replica costs
  the fleet capacity, never tail latency;
* application errors (``QueueFull``, ``EngineDraining``, ...) cross the
  wire as HTTP 409 + exception class name and are re-raised as the real
  admission types — they are ANSWERS from a live worker, so they count
  as breaker successes and are never retried.

Failure taxonomy the router keys off:

* :class:`ReplicaUnavailable` — transport-level: deadline, refused
  connection, chaos partition, breaker open. The replica may be fine;
  degrade (skip this round) rather than declare death.
* :class:`ReplicaDead` — the worker PROCESS exited (``Popen.poll()``
  non-None). Unambiguous: trigger failover.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from typing import Callable, Dict, List, Optional, Sequence

from distributed_pytorch_tpu.obs.server import scrape
from distributed_pytorch_tpu.serving import admission as _admission
from distributed_pytorch_tpu.serving.admission import AdmissionError
from distributed_pytorch_tpu.serving.elastic import (
    EngineSnapshot,
    adopt_snapshot,
    drain_engine,
    fetch_snapshot_text,
    params_to_doc,
    restore_engine,
)
from distributed_pytorch_tpu.serving.journal import (
    remove_worker_entry,
    write_worker_entry,
)
from distributed_pytorch_tpu.serving.engine import RequestStatus
from distributed_pytorch_tpu.serving.scheduler import SamplingParams
from distributed_pytorch_tpu.utils.platform import (
    holds_accelerator,
    workers_pinned_to_cpu,
)

_JSON = "application/json"


class ReplicaError(RuntimeError):
    """Base for replica control-plane failures."""


class ReplicaUnavailable(ReplicaError):
    """Transport-level failure: call deadline, refused/reset connection,
    chaos partition, or a fast-fail from an open circuit breaker. The
    worker process may well be alive — callers should degrade (skip the
    replica this round), not declare it dead."""


class ReplicaDead(ReplicaError):
    """The replica worker PROCESS exited. ``reason`` carries the best
    attribution the client has: the chaos kind that killed it when the
    client itself delivered the signal, else ``"process_exit"``."""

    def __init__(self, msg: str, *, reason: str = "process_exit"):
        super().__init__(msg)
        self.reason = reason


# ------------------------------------------------------------------ breaker


class CircuitBreaker:
    """Per-replica circuit breaker over control-plane transport health.

    Classic three-state machine, driven entirely by the client's
    record_success/record_failure calls:

    * ``closed`` — normal operation. ``fail_threshold`` CONSECUTIVE
      failures trip it open (one success resets the streak: a flaky link
      is not a dead one).
    * ``open`` — every :meth:`allow` is refused for ``reset_timeout_s``
      (callers fast-fail with :class:`ReplicaUnavailable`, spending zero
      deadline budget on a replica known to be wedged).
    * ``half_open`` — after the cooldown, :meth:`allow` grants exactly ONE
      in-flight probe; its success closes the breaker, its failure
      re-opens it and restarts the cooldown.

    The clock is injectable for deterministic state-machine tests; the
    in-process :class:`LocalReplicaClient` constructs a disabled breaker
    (``enabled=False``) that never opens, since a same-process call
    cannot time out at the transport."""

    def __init__(
        self,
        *,
        fail_threshold: int = 3,
        reset_timeout_s: float = 1.0,
        clock: Callable[[], float] = time.monotonic,
        enabled: bool = True,
    ):
        if fail_threshold < 1:
            raise ValueError("fail_threshold must be >= 1")
        self.fail_threshold = fail_threshold
        self.reset_timeout_s = reset_timeout_s
        self.enabled = enabled
        self._clock = clock
        self._failures = 0
        self._opened_at: Optional[float] = None
        self._probe_inflight = False
        self.opens_total = 0
        self.closes_total = 0

    @property
    def state(self) -> str:
        if self._opened_at is None:
            return "closed"
        if self._clock() - self._opened_at >= self.reset_timeout_s:
            return "half_open"
        return "open"

    def allow(self) -> bool:
        """May a call go out right now? half-open grants one probe."""
        st = self.state
        if st == "closed":
            return True
        if st == "open":
            return False
        if self._probe_inflight:
            return False
        self._probe_inflight = True
        return True

    def record_success(self) -> None:
        if self._opened_at is not None:
            self.closes_total += 1
        self._failures = 0
        self._opened_at = None
        self._probe_inflight = False

    def record_failure(self) -> None:
        if not self.enabled:
            return
        if self._opened_at is not None:
            # Half-open probe failed (or a straggler failure landed while
            # open): re-open and restart the cooldown.
            self._opened_at = self._clock()
            self._probe_inflight = False
            return
        self._failures += 1
        if self._failures >= self.fail_threshold:
            self._opened_at = self._clock()
            self._probe_inflight = False
            self.opens_total += 1


# ---------------------------------------------------------------- interface


class ReplicaClient:
    """What the router needs from one replica, local or cross-process.

    Data-plane: :meth:`submit` / :meth:`step` / :meth:`poll` /
    :meth:`cancel`. Elastic: :meth:`drain` / :meth:`restore` /
    :meth:`adopt` (publish/adopt KV hand-off). Observability:
    :meth:`health`, :meth:`load`, :meth:`read_gauge`,
    :meth:`metrics_snapshot` (the ``merge_remote`` payload),
    :meth:`describe`, :meth:`trace_documents`, :meth:`slo_firing`,
    :meth:`idle_fraction`. Chaos (process implementations only — the
    router falls back to in-process semantics when ``is_process`` is
    False): :meth:`kill`, :meth:`suspend`, :meth:`partition`."""

    kind = "?"
    is_process = False
    #: The wrapped in-process engine, or None for a cross-process replica.
    #: Exposed (rather than hidden) so local fleets keep their exact
    #: pre-refactor surface — tests and drills reach through
    #: ``replica.engine`` for gauges and even setattr SLO trackers.
    engine = None
    breaker: CircuitBreaker
    #: monotonic timestamp the client delivered a chaos kill, if any —
    #: the router uses it as time-of-death for detection-latency gauges.
    killed_at: Optional[float] = None

    # -- identity / setup
    @property
    def url(self) -> Optional[str]:
        raise NotImplementedError

    def fingerprint(self) -> dict:
        raise NotImplementedError

    def reserve_ids(self, base: int) -> None:
        raise NotImplementedError

    def start_server(self) -> str:
        raise NotImplementedError

    # -- data plane
    def submit(self, prompt, params=None, metadata=None, *,
               tenant_id: str = "anon", mods=None,
               trace_id: Optional[str] = None) -> int:
        raise NotImplementedError

    def step(self) -> List[int]:
        raise NotImplementedError

    def poll(self, req_id: int) -> RequestStatus:
        raise NotImplementedError

    def cancel(self, req_id: int) -> bool:
        raise NotImplementedError

    # -- elastic
    def drain(self, reason: str = "drain") -> EngineSnapshot:
        raise NotImplementedError

    def restore(self, snapshot: EngineSnapshot, *,
                rebase_ids: bool = False) -> List[int]:
        raise NotImplementedError

    def adopt(self, store, key: str, *, delete: bool = True,
              rebase_ids: bool = False,
              timeout_s: Optional[float] = None) -> List[int]:
        raise NotImplementedError

    # -- observability
    def health(self, timeout_s: Optional[float] = None) -> str:
        raise NotImplementedError

    def load(self) -> float:
        raise NotImplementedError

    def queue_depth(self) -> float:
        raise NotImplementedError

    def read_gauge(self, name: str) -> float:
        raise NotImplementedError

    def metrics_snapshot(self) -> Optional[dict]:
        raise NotImplementedError

    def describe(self) -> dict:
        raise NotImplementedError

    def trace_documents(self) -> List[dict]:
        raise NotImplementedError

    def slo_firing(self) -> List[str]:
        raise NotImplementedError

    def idle_fraction(self) -> Optional[float]:
        raise NotImplementedError

    # -- lifecycle
    def close(self) -> None:
        """Graceful stop: drain nothing, just release resources."""
        raise NotImplementedError

    def abandon(self) -> None:
        """Tear down a replica declared dead: reap/kill the child if any,
        stop servers. Never raises."""
        raise NotImplementedError

    # -- chaos delivery (process clients only)
    def kill(self, *, chaos_kind: str = "kill_replica_process") -> None:
        raise NotImplementedError

    def suspend(self, duration_s: float = 0.0) -> None:
        raise NotImplementedError

    def resume(self) -> None:
        raise NotImplementedError

    def partition(self, duration_s: float = 0.0) -> None:
        raise NotImplementedError

    def heal(self) -> None:
        raise NotImplementedError


# -------------------------------------------------------------- local client


class LocalReplicaClient(ReplicaClient):
    """In-process replica: a thin delegate around ``InferenceEngine``.

    Every call lands directly on the engine object with zero translation,
    so a fleet of local clients is behaviorally identical to the
    pre-refactor router holding bare engines. The breaker is constructed
    disabled — an in-process call cannot fail at the transport — so
    breaker-aware routing logic treats local replicas as always-closed
    without special-casing."""

    kind = "local"
    is_process = False

    def __init__(self, engine, *, serve: bool = False):
        self.engine = engine
        self.breaker = CircuitBreaker(enabled=False)
        self.killed_at = None
        if serve:
            engine.serve()

    @property
    def url(self) -> Optional[str]:
        server = getattr(self.engine, "_server", None)
        return server.url if server is not None else None

    def start_server(self) -> str:
        return self.engine.serve().url

    def fingerprint(self) -> dict:
        e = self.engine
        return {
            "page_size": e.page_size,
            "max_seq_len": e.max_seq_len,
            "top_k": e._top_k,
            "top_p": e._top_p,
            "speculative": e.speculative,
            "mesh": e.mesh_fingerprint,
            "kv": e.kv_fingerprint,
        }

    def reserve_ids(self, base: int) -> None:
        self.engine._next_id = max(self.engine._next_id, base)

    def submit(self, prompt, params=None, metadata=None, *,
               tenant_id="anon", mods=None, trace_id=None) -> int:
        return self.engine.submit(
            prompt, params, metadata, tenant_id=tenant_id, mods=mods,
            trace_id=trace_id,
        )

    def step(self) -> List[int]:
        return self.engine.step()

    def poll(self, req_id: int) -> RequestStatus:
        return self.engine.poll(req_id)

    def cancel(self, req_id: int) -> bool:
        return self.engine.cancel(req_id)

    def drain(self, reason: str = "drain") -> EngineSnapshot:
        return drain_engine(self.engine, reason=reason)

    def restore(self, snapshot, *, rebase_ids=False) -> List[int]:
        return restore_engine(self.engine, snapshot, rebase_ids=rebase_ids)

    def adopt(self, store, key, *, delete=True, rebase_ids=False,
              timeout_s=None) -> List[int]:
        return adopt_snapshot(
            self.engine, store, key, delete=delete, rebase_ids=rebase_ids,
            timeout_s=timeout_s,
        )

    def health(self, timeout_s: Optional[float] = None) -> str:
        url = self.url
        if url is not None:
            doc = scrape(
                url, "/healthz", timeout=timeout_s or 1.0, retries=0
            )
            return doc.get("status", "dead")
        return self.engine.health()

    def load(self) -> float:
        reg = self.engine.registry
        return (
            reg.read_gauge("queue_depth")
            + reg.read_gauge("running_requests")
        )

    def queue_depth(self) -> float:
        return self.engine.registry.read_gauge("queue_depth")

    def read_gauge(self, name: str) -> float:
        return self.engine.registry.read_gauge(name)

    def metrics_snapshot(self) -> Optional[dict]:
        return self.engine.registry.snapshot(include_state=True)

    def describe(self) -> dict:
        return self.engine.status()

    def trace_documents(self) -> List[dict]:
        tracer = getattr(self.engine, "tracer", None)
        if tracer is None or not tracer.enabled:
            return []
        with self.engine.registry.lock:
            return [json.loads(json.dumps(tracer.to_perfetto()))]

    def slo_firing(self) -> List[str]:
        slo = getattr(self.engine, "slo", None)
        if slo is None:
            return []
        return [
            name for name, st in slo.state().items() if st["firing"]
        ]

    def idle_fraction(self) -> Optional[float]:
        goodput = getattr(self.engine, "goodput", None)
        if goodput is None:
            return None
        total = goodput.productive_s + goodput.wasted_total_s()
        if total <= 0:
            return None
        return goodput.wasted["budget_idle"] / total

    def close(self) -> None:
        self.engine.close()

    def abandon(self) -> None:
        # A dead local replica's engine object is abandoned un-closed
        # (the in-process SIGKILL analogy) — but its obs server thread is
        # real and must stop.
        server = getattr(self.engine, "_server", None)
        if server is not None:
            try:
                server.stop()
            except Exception:
                pass


# ------------------------------------------------------------ process client


#: Control-plane ops safe to retry on transport failure. ``submit`` and
#: ``cancel`` qualify because the worker dedups them through a replay map
#: keyed by a client-minted request id; ``poll``/``health``/``describe``
#: are read-only; ``adopt`` converges (claiming an already-claimed worker
#: is a no-op answer). ``step`` is deliberately absent (see module
#: docstring).
_IDEMPOTENT = frozenset({
    "/submit", "/cancel", "/poll", "/health", "/describe", "/gauge",
    "/reserve_ids", "/adopt",
})

_HELLO_KEY = "replica_hello"


def _status_from_doc(doc: dict) -> RequestStatus:
    return RequestStatus(
        req_id=int(doc["req_id"]),
        state=doc["state"],
        prompt_len=int(doc["prompt_len"]),
        generated=[int(t) for t in doc["generated"]],
        finished=bool(doc["finished"]),
        preempt_count=int(doc.get("preempt_count", 0)),
    )


def _params_to_doc(params: SamplingParams) -> dict:
    # One canonical codec (elastic.params_to_doc) serves the control-plane
    # wire AND the router's write-ahead journal, so a journaled submit can
    # be re-submitted byte-identically after a router crash.
    return params_to_doc(params)


class _PidProcess:
    """``Popen`` look-alike over a bare pid, for ATTACHING to a worker
    this process never spawned (router crash recovery re-adopts workers
    the DEAD router's registry points at). Implements exactly the surface
    :class:`ProcessReplicaClient` touches — ``poll``/``wait``/
    ``terminate``/``kill``, ``.pid``/``.returncode``, ``None`` pipes.
    A non-child cannot be ``waitpid``-ed, so liveness is probed with
    ``kill(pid, 0)`` and death reported as returncode ``-1`` (the true
    exit code belongs to whoever reaped it)."""

    def __init__(self, pid: int):
        self.pid = int(pid)
        self.returncode: Optional[int] = None
        self.stdin = None
        self.stdout = None

    def poll(self) -> Optional[int]:
        if self.returncode is not None:
            return self.returncode
        try:
            os.kill(self.pid, 0)
        except ProcessLookupError:
            self.returncode = -1
        except PermissionError:
            pass  # exists, owned by someone else: alive
        except OSError:
            self.returncode = -1
        else:
            # ``kill(pid, 0)`` succeeds on a ZOMBIE — an exited worker
            # whose (still-living) spawner has not reaped it yet. That
            # worker is gone for every purpose this shim serves.
            if self._is_zombie():
                self.returncode = -1
        return self.returncode

    def _is_zombie(self) -> bool:
        try:
            with open(f"/proc/{self.pid}/stat", "rb") as f:
                stat = f.read()
            # Field 3, after the parenthesized (possibly space-laden) comm.
            return stat.rpartition(b")")[2].split()[0] == b"Z"
        except (OSError, IndexError):
            return False  # no procfs: fall back to kill(0) semantics

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = (
            None if timeout is None else time.monotonic() + timeout
        )
        while self.poll() is None:
            if deadline is not None and time.monotonic() >= deadline:
                raise subprocess.TimeoutExpired(
                    f"pid {self.pid}", timeout
                )
            time.sleep(0.02)
        return self.returncode

    def terminate(self) -> None:
        try:
            os.kill(self.pid, signal.SIGTERM)
        except OSError:
            pass

    def kill(self) -> None:
        try:
            os.kill(self.pid, signal.SIGKILL)
        except OSError:
            pass


class ProcessReplicaClient(ReplicaClient):
    """Drives one replica worker subprocess over localhost HTTP.

    Spawn mirrors the elastic agent's WorkerGroup: the worker inherits a
    scrubbed environment (the chaos plan env var is STRIPPED — faults are
    delivered by the router through this client, never re-armed inside
    the child), gets its spec as one env JSON blob, and announces
    readiness with a single hello line on stdout carrying its
    kernel-assigned control and introspection ports. Shutdown mirrors
    ``WorkerGroup.terminate``: polite ``/shutdown`` (the worker closes
    its engine — leak asserts run there and surface as a non-zero exit),
    then SIGTERM, then SIGKILL.

    A daemon thread pumps the child's stdout for its lifetime (tail kept
    for diagnostics); the child watches its stdin for EOF and exits if
    the parent dies first, so no drill can leak an orphan worker."""

    kind = "process"
    is_process = True
    engine = None

    def __init__(
        self,
        spec: dict,
        *,
        name: Optional[str] = None,
        python: str = sys.executable,
        spawn_timeout_s: float = 120.0,
        call_timeout_s: float = 10.0,
        step_timeout_s: Optional[float] = None,
        drain_timeout_s: float = 60.0,
        call_retries: int = 2,
        retry_backoff_s: float = 0.05,
        breaker_fail_threshold: int = 3,
        breaker_reset_s: float = 1.0,
        env: Optional[Dict[str, str]] = None,
        clock: Callable[[], float] = time.perf_counter,
        run_dir: Optional[str] = None,
        attach_entry: Optional[dict] = None,
    ):
        if attach_entry is not None and name is None:
            name = attach_entry.get("name")
        self.name = name or spec.get("name") or "replica"
        self.run_dir = run_dir
        self.spec = spec
        self.call_timeout_s = call_timeout_s
        self.step_timeout_s = step_timeout_s or call_timeout_s
        self.drain_timeout_s = drain_timeout_s
        self.call_retries = call_retries
        self.retry_backoff_s = retry_backoff_s
        self._clock = clock
        self.breaker = CircuitBreaker(
            fail_threshold=breaker_fail_threshold,
            reset_timeout_s=breaker_reset_s,
            clock=clock,
        )
        self.killed_at: Optional[float] = None
        self._chaos_kind: Optional[str] = None
        self._partitioned_until: Optional[float] = None
        self._suspended = False
        self._rids = itertools.count()
        self._nonce = f"{os.getpid():x}-{random.randrange(1 << 30):x}"
        self._statuses: Dict[int, RequestStatus] = {}
        self._to_ack: List[int] = []
        self._load = 0.0
        self._queue_depth = 0.0
        self._slo_firing: List[str] = []
        self._idle_fraction: Optional[float] = None
        self._last_trace: Optional[dict] = None
        self._last_metrics: Optional[dict] = None
        self._log_tail: collections.deque = collections.deque(maxlen=100)
        self._hello: Optional[dict] = None
        self._hello_event = threading.Event()
        #: True when this client ATTACHED to an orphaned worker (router
        #: recovery) rather than spawning it; the recovery summary counts
        #: these as re-adoptions.
        self.adopted = False

        if attach_entry is not None:
            self._attach(attach_entry)
            return

        child_env = dict(os.environ if env is None else env)
        if holds_accelerator() and not workers_pinned_to_cpu(child_env):
            # A chip belongs to one process: this one has opened it, so the
            # worker would die at backend start-up ("already in use") or,
            # on a host with several chips, claim all of them. Refuse now
            # rather than after the spawn timeout.
            raise ReplicaError(
                f"cannot spawn replica worker {self.name}: this process "
                "already holds the accelerator, and a worker subprocess "
                "needs a device of its own. Start workers from a parent "
                "that has not initialised JAX, pin them to the CPU "
                "(JAX_PLATFORMS=cpu in env), or use in-process replicas "
                "(LocalReplicaClient)."
            )
        # Chaos plans are delivered by the ROUTER through this client —
        # a worker that also armed the plan would double-fire every fault.
        child_env.pop("TPURUN_FAULT_PLAN", None)
        child_env["TPURUN_REPLICA_SPEC"] = json.dumps(spec)
        child_env["TPURUN_REPLICA_NAME"] = self.name
        self._proc = subprocess.Popen(
            [python, "-m",
             "distributed_pytorch_tpu.serving.replica_worker"],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=child_env,
            text=True,
        )
        self._pump = threading.Thread(
            target=self._pump_stdout,
            name=f"replica-pump-{self.name}",
            daemon=True,
        )
        self._pump.start()
        if not self._hello_event.wait(spawn_timeout_s):
            tail = "\n".join(self._log_tail)
            self.abandon()
            raise ReplicaError(
                f"replica worker {self.name} never said hello within "
                f"{spawn_timeout_s:.0f}s; last output:\n{tail}"
            )
        if self._hello is None:
            code = self._proc.poll()
            tail = "\n".join(self._log_tail)
            raise ReplicaDead(
                f"replica worker {self.name} exited (code {code}) before "
                f"hello; last output:\n{tail}"
            )
        self.control_url: str = self._hello["control_url"]
        self.obs_url: str = self._hello["obs_url"]
        self.pid: int = int(self._hello["pid"])
        self._fingerprint: dict = dict(self._hello["fingerprint"])
        self._write_registry_entry()

    @classmethod
    def attach(cls, entry: dict, **kwargs) -> "ProcessReplicaClient":
        """Re-adopt a LIVE worker from its registry entry instead of
        spawning one — the router-recovery path. The entry must carry
        ``pid``/``control_url``/``obs_url``/``fingerprint`` (what
        :meth:`_write_registry_entry` persists); the worker is claimed
        and identity-checked via ``POST /adopt``, which refuses (409 →
        ``ValueError`` here) if the pid was reborn as a different
        process or the spec fingerprint disagrees."""
        return cls(
            dict(entry.get("spec") or {}), attach_entry=entry, **kwargs
        )

    def _attach(self, entry: dict) -> None:
        self._proc = _PidProcess(int(entry["pid"]))
        self._pump = None
        self._hello = dict(entry)
        self._hello_event.set()
        self.control_url = entry["control_url"]
        self.obs_url = entry["obs_url"]
        self.pid = int(entry["pid"])
        self._fingerprint = dict(entry.get("fingerprint") or {})
        self._check_alive()  # pid already gone: ReplicaDead, not a probe
        doc = self._call("/adopt", {
            "name": self.name,
            "pid": self.pid,
            "fingerprint": self._fingerprint or None,
        })
        self.adopted = True
        self.adopted_orphan = bool(doc.get("orphaned"))
        self._write_registry_entry()

    # ------------------------------------------------------------ registry

    def _write_registry_entry(self) -> None:
        """Persist this worker's coordinates for a successor router.

        The entry is the recovery bootstrap: everything
        :meth:`attach` needs to re-adopt the worker after THIS router
        process is gone. Written on spawn and refreshed on attach; removed
        on deliberate teardown (:meth:`close` / :meth:`abandon`) so the
        registry only ever lists workers somebody should re-adopt."""
        if self.run_dir is None:
            return
        write_worker_entry(self.run_dir, {
            "name": self.name,
            "pid": self.pid,
            "control_url": self.control_url,
            "obs_url": self.obs_url,
            "fingerprint": self._fingerprint,
            "spec": self.spec,
            "written_s": time.time(),
        })

    def _remove_registry_entry(self) -> None:
        if self.run_dir is not None:
            remove_worker_entry(self.run_dir, self.name)

    # ------------------------------------------------------------ plumbing

    def _pump_stdout(self) -> None:
        stream = self._proc.stdout
        try:
            for line in stream:
                line = line.rstrip("\n")
                if (not self._hello_event.is_set()
                        and line.startswith("{")
                        and _HELLO_KEY in line):
                    try:
                        self._hello = json.loads(line)[_HELLO_KEY]
                    except (ValueError, KeyError):
                        self._log_tail.append(line)
                    else:
                        self._hello_event.set()
                        continue
                self._log_tail.append(line)
        except (ValueError, OSError):
            pass  # stream closed under us during teardown
        finally:
            self._hello_event.set()

    def _check_alive(self) -> None:
        code = self._proc.poll()
        if code is not None:
            raise ReplicaDead(
                f"replica worker {self.name} exited with code {code}",
                reason=self._chaos_kind or "process_exit",
            )

    def _app_error(self, code: int, payload: dict) -> Exception:
        kind = payload.get("error_kind", "")
        msg = payload.get("error", f"HTTP {code}")
        cls = getattr(_admission, kind, None)
        if isinstance(cls, type) and issubclass(cls, AdmissionError):
            return cls(msg)
        if kind == "KeyError":
            return KeyError(msg)
        if kind == "ValueError":
            return ValueError(msg)
        return ReplicaError(f"{self.name}: {kind or code}: {msg}")

    def _call(self, endpoint: str, body: Optional[dict] = None, *,
              timeout_s: Optional[float] = None) -> dict:
        """One control-plane call with the full robustness stack: breaker
        gate, chaos-partition check, liveness check, per-call deadline,
        and jittered-exponential retries for idempotent endpoints."""
        now = self._clock()
        if self._partitioned_until is not None:
            if 0 < self._partitioned_until <= now:
                self._partitioned_until = None  # healed
            else:
                self.breaker.record_failure()
                raise ReplicaUnavailable(
                    f"{self.name}: control socket partitioned (chaos)"
                )
        if not self.breaker.allow():
            raise ReplicaUnavailable(
                f"{self.name}: circuit breaker {self.breaker.state}"
            )
        op = endpoint.split("?", 1)[0]
        attempts = 1 + (self.call_retries if op in _IDEMPOTENT else 0)
        delay = self.retry_backoff_s
        timeout = timeout_s if timeout_s is not None else self.call_timeout_s
        for attempt in range(attempts):
            self._check_alive()
            try:
                if body is not None:
                    data = json.dumps(body).encode("utf-8")
                    req = urllib.request.Request(
                        self.control_url + endpoint, data=data,
                        headers={"Content-Type": _JSON}, method="POST",
                    )
                else:
                    req = urllib.request.Request(
                        self.control_url + endpoint, method="GET"
                    )
                with urllib.request.urlopen(req, timeout=timeout) as resp:
                    doc = json.loads(resp.read().decode("utf-8"))
            except urllib.error.HTTPError as err:
                # The worker ANSWERED — an application error from a live
                # replica, not a transport failure.
                self.breaker.record_success()
                try:
                    payload = json.loads(err.read().decode("utf-8"))
                except ValueError:
                    payload = {}
                raise self._app_error(err.code, payload) from None
            except OSError as exc:
                # URLError (refused/reset) and socket timeouts are both
                # OSError subclasses. Re-check liveness first: a refused
                # connect from an exited child is death, not flakiness.
                self._check_alive()
                self.breaker.record_failure()
                if attempt + 1 < attempts and self.breaker.allow():
                    time.sleep(delay * (0.5 + random.random() * 0.5))
                    delay = min(delay * 2.0, 1.0)
                    continue
                raise ReplicaUnavailable(
                    f"{self.name}: {op} failed after {attempt + 1} "
                    f"attempt(s): {exc}"
                ) from exc
            else:
                self.breaker.record_success()
                return doc
        raise AssertionError("unreachable")

    def _ingest_statuses(self, docs) -> None:
        if not docs:
            return
        for entry in docs:
            st = _status_from_doc(entry)
            self._statuses[st.req_id] = st

    # ---------------------------------------------------------- interface

    @property
    def url(self) -> Optional[str]:
        return self.obs_url

    def start_server(self) -> str:
        return self.obs_url  # the worker always serves introspection

    def fingerprint(self) -> dict:
        return dict(self._fingerprint)

    def reserve_ids(self, base: int) -> None:
        self._call("/reserve_ids", {"base": int(base)})

    def submit(self, prompt, params=None, metadata=None, *,
               tenant_id="anon", mods=None, trace_id=None) -> int:
        params = params if params is not None else SamplingParams()
        rid = f"{self._nonce}-{next(self._rids)}"
        doc = self._call("/submit", {
            "rid": rid,
            "prompt": [int(t) for t in prompt],
            "params": _params_to_doc(params),
            "metadata": metadata,
            "tenant_id": tenant_id,
            "mods": mods.to_spec() if mods is not None else None,
            "trace_id": trace_id,
        })
        return int(doc["req_id"])

    def step(self) -> List[int]:
        doc = self._call(
            "/step", {"ack": self._to_ack},
            timeout_s=self.step_timeout_s,
        )
        self._ingest_statuses(doc.get("statuses"))
        self._load = float(doc.get("load", 0.0))
        self._queue_depth = float(doc.get("queue_depth", 0.0))
        self._slo_firing = list(doc.get("slo_firing", []))
        self._idle_fraction = doc.get("idle_fraction")
        if doc.get("trace") is not None:
            self._last_trace = doc["trace"]
        finished = [int(i) for i in doc.get("finished", [])]
        # At-least-once finish delivery: ack what we just consumed so the
        # worker stops re-reporting it. A step RESPONSE lost in transport
        # re-delivers these ids next round; ids are deduped router-side.
        self._to_ack = finished
        return finished

    def poll(self, req_id: int) -> RequestStatus:
        st = self._statuses.get(req_id)
        if st is not None:
            return st
        doc = self._call(f"/poll?id={int(req_id)}")
        st = _status_from_doc(doc)
        self._statuses[req_id] = st
        return st

    def cancel(self, req_id: int) -> bool:
        doc = self._call("/cancel", {"req_id": int(req_id)})
        ok = bool(doc["ok"])
        if ok:
            # The cached status predates the cancel; evict it so the next
            # poll fetches the terminal (cancelled) state from the worker.
            self._statuses.pop(int(req_id), None)
        return ok

    def drain(self, reason: str = "drain") -> EngineSnapshot:
        doc = self._call(
            "/drain", {"reason": reason}, timeout_s=self.drain_timeout_s
        )
        self._ingest_statuses(doc.get("statuses"))
        return EngineSnapshot.from_json(doc["snapshot"])

    def restore(self, snapshot, *, rebase_ids=False) -> List[int]:
        doc = self._call("/restore", {
            "snapshot": snapshot.to_json(),
            "rebase_ids": bool(rebase_ids),
        }, timeout_s=self.drain_timeout_s)
        return [int(i) for i in doc["restored"]]

    def adopt(self, store, key, *, delete=True, rebase_ids=False,
              timeout_s=None) -> List[int]:
        # Parent-side fetch (the worker has no store credentials), then
        # one restore over the control plane. delete only after the
        # restore is acknowledged: adopt-once must not drop the snapshot
        # if the worker refuses it.
        if timeout_s is None:
            text = store.get(key)
            if text is None:
                return []
        else:
            text = fetch_snapshot_text(store, key, timeout_s=timeout_s)
        ids = self.restore(
            EngineSnapshot.from_json(text), rebase_ids=rebase_ids
        )
        if delete:
            store.delete(key)
        return ids

    def health(self, timeout_s: Optional[float] = None) -> str:
        doc = self._call("/health", timeout_s=timeout_s)
        return doc["status"]

    def load(self) -> float:
        return self._load

    def queue_depth(self) -> float:
        return self._queue_depth

    def read_gauge(self, name: str) -> float:
        doc = self._call(f"/gauge?name={urllib.parse.quote(name)}")
        return float(doc["value"])

    def metrics_snapshot(self) -> Optional[dict]:
        try:
            self._check_alive()
            snap = scrape(self.obs_url, "/snapshot", retries=0)
        except (ReplicaDead, OSError):
            return self._last_metrics  # best effort: last good scrape
        self._last_metrics = snap
        return snap

    def describe(self) -> dict:
        return self._call("/describe")

    def trace_documents(self) -> List[dict]:
        try:
            self._check_alive()
            doc = scrape(self.obs_url, "/trace", retries=0)
        except ReplicaDead:
            # The victim's interpreter is gone, but its last trace doc —
            # cached from step responses — keeps its lanes in the merged
            # fleet waterfall.
            return [self._last_trace] if self._last_trace else []
        except urllib.error.HTTPError:
            return []  # 404: worker runs untraced
        except OSError:
            return [self._last_trace] if self._last_trace else []
        if isinstance(doc, dict):
            self._last_trace = doc
            return [doc]
        return []

    def slo_firing(self) -> List[str]:
        return list(self._slo_firing)

    def idle_fraction(self) -> Optional[float]:
        return self._idle_fraction

    # ----------------------------------------------------------- lifecycle

    def close(self, timeout_s: float = 30.0) -> None:
        """Polite shutdown: ``/shutdown`` runs ``engine.close()`` INSIDE
        the worker — debug-mode allocator leak asserts run there, and a
        failure comes back as an HTTP 500 (raised here as ReplicaError)
        plus a non-zero exit. Escalates SIGTERM → SIGKILL like
        ``WorkerGroup.terminate`` if the child lingers."""
        err: Optional[Exception] = None
        if self._proc.poll() is None and self._partitioned_until is None:
            self.resume()  # a SIGSTOPped child cannot run /shutdown
            try:
                self._call("/shutdown", {}, timeout_s=timeout_s)
            except ReplicaError as exc:
                err = exc
        try:
            self._proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self._proc.terminate()
            try:
                self._proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                self._proc.kill()
                self._proc.wait(timeout=5.0)
        self._release_pipes()
        self._remove_registry_entry()
        code = self._proc.returncode
        if err is not None:
            raise ReplicaError(
                f"replica worker {self.name} failed to close cleanly "
                f"(exit {code}): {err}"
            ) from err
        if (
            code not in (0, None)
            and self._chaos_kind is None
            # An attached (non-child) worker cannot be reaped, so its
            # true exit code is unknowable; -1 there means "gone", not
            # "failed".
            and not isinstance(self._proc, _PidProcess)
        ):
            tail = "\n".join(self._log_tail)
            raise ReplicaError(
                f"replica worker {self.name} exited {code} on close; "
                f"last output:\n{tail}"
            )

    def abandon(self) -> None:
        try:
            if self._proc.poll() is None:
                # SIGCONT first: SIGKILL terminates a stopped process,
                # but be explicit so a SIGSTOPped child reaps promptly.
                try:
                    os.kill(self._proc.pid, signal.SIGCONT)
                except OSError:
                    pass
                self._proc.kill()
            self._proc.wait(timeout=5.0)
        except Exception:
            pass
        self._release_pipes()
        self._remove_registry_entry()

    def _release_pipes(self) -> None:
        for stream in (self._proc.stdin, self._proc.stdout):
            try:
                if stream is not None:
                    stream.close()
            except OSError:
                pass

    # --------------------------------------------------------------- chaos

    def kill(self, *, chaos_kind: str = "kill_replica_process") -> None:
        """Deliver a REAL SIGKILL to the worker. Records time-of-death so
        the router's detection-latency gauge measures kill → first failed
        contact, same as the in-process drills."""
        self._chaos_kind = chaos_kind
        self.killed_at = self._clock()
        try:
            os.kill(self._proc.pid, signal.SIGKILL)
        except OSError:
            pass

    def suspend(self, duration_s: float = 0.0) -> None:
        """SIGSTOP the worker — the truest 'hung but alive' fault: the
        kernel keeps its sockets open, connects succeed, reads stall until
        the call deadline. ``duration_s > 0`` schedules the SIGCONT."""
        self._suspended = True
        try:
            os.kill(self._proc.pid, signal.SIGSTOP)
        except OSError:
            return
        if duration_s > 0:
            timer = threading.Timer(duration_s, self.resume)
            timer.daemon = True
            timer.start()

    def resume(self) -> None:
        if not self._suspended:
            return
        self._suspended = False
        if self._proc.poll() is None:
            try:
                os.kill(self._proc.pid, signal.SIGCONT)
            except OSError:
                pass

    def partition(self, duration_s: float = 0.0) -> None:
        """Black-hole the control socket CLIENT-side: every call fails
        instantly as :class:`ReplicaUnavailable` (and feeds the breaker)
        until ``duration_s`` elapses — 0 means until :meth:`heal`."""
        self._partitioned_until = (
            self._clock() + duration_s if duration_s > 0 else float("inf")
        )

    def heal(self) -> None:
        self._partitioned_until = None


def spawn_replica_clients(
    specs: Sequence[dict], **kwargs
) -> List[ProcessReplicaClient]:
    """Spawn one :class:`ProcessReplicaClient` per spec CONCURRENTLY.

    Worker start-up is dominated by the child's JAX import + XLA warm-up
    compile, which parallelizes perfectly across processes — a 3-replica
    fleet spawns in roughly the time of one. ``kwargs`` go to every
    constructor (deadlines, breaker tuning). All-or-nothing: if any spawn
    fails, the ones that succeeded are abandoned and the first error is
    re-raised."""
    clients: List[Optional[ProcessReplicaClient]] = [None] * len(specs)
    errors: List[Optional[BaseException]] = [None] * len(specs)

    def _spawn(i: int, spec: dict) -> None:
        try:
            clients[i] = ProcessReplicaClient(spec, **kwargs)
        except BaseException as exc:  # noqa: BLE001 - reported below
            errors[i] = exc

    threads = [
        threading.Thread(
            target=_spawn, args=(i, spec),
            name=f"replica-spawn-{i}", daemon=True,
        )
        for i, spec in enumerate(specs)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    first_error = next((e for e in errors if e is not None), None)
    if first_error is not None:
        for c in clients:
            if c is not None:
                c.abandon()
        raise first_error
    return [c for c in clients if c is not None]


__all__ = [
    "CircuitBreaker",
    "LocalReplicaClient",
    "ProcessReplicaClient",
    "ReplicaClient",
    "ReplicaDead",
    "ReplicaError",
    "ReplicaUnavailable",
    "spawn_replica_clients",
]
