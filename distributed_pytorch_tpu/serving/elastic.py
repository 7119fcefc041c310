"""Elastic serving: drain protocol, engine snapshot/restore codec, and the
serving half of the chaos drills.

The training stack already survives reclaims (SIGTERM drain marks,
step-granular snapshots); this module gives the inference engine the same
story. The key observation is that the engine's preemption path ALREADY
proves most of it: a preempted request keeps its generated tokens, releases
its pages, and resumes token-identically on re-admission, because

* greedy decode is a pure function of (params, tokens), and
* a sampled request draws token i with ``fold_in(PRNGKey(seed), n_issued)``
  where ``n_issued`` counts from ``len(prompt)`` — independent of batch
  composition, slot assignment, and restarts.

Restore is therefore "re-admission on a fresh engine": the snapshot records
HOST state only — prompt, committed generated tokens, sampling params,
tenant-opaque metadata, deadline age — plus just enough KV metadata
(committed token count and the content-addressed prefix-trie key chain of
the request's cached pages) for capacity planning on the restore side.
Device pages are deliberately NOT persisted: the restored engine re-prefills
prompt+generated through its prefix cache, so a fleet of requests sharing a
system prompt re-pays that prefix once, not per request.

In-flight work at snapshot time is ROLLED BACK, not awaited: any token
whose device readback never landed (a PENDING placeholder under overlap, an
unresolved draft+verify round) is simply absent from the snapshot, and the
restored engine re-issues the identical dispatch — same fold index, same
sample. A clean drain (:func:`drain_engine`) first finishes the in-flight
step so nothing is re-paid; a kill recovers from the last rolling snapshot
and re-generates the (identical) tail.

:class:`DrainController` wires this into a process: it installs a SIGTERM
handler (the reclaim notice — also what the serving chaos fault kinds
deliver in "hard" mode), drives the engine step loop, drains on notice, and
optionally writes rolling snapshots so even an uncatchable SIGKILL loses
nothing admitted. :func:`publish_snapshot` / :func:`adopt_snapshot` hand a
drained engine's queue to a peer replica through the elastic KV store.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import time
from typing import Dict, List, Optional, Tuple

from distributed_pytorch_tpu import chaos
from distributed_pytorch_tpu.generation import host_prng_key
from distributed_pytorch_tpu.obs.tracer import _PID_REQUESTS
from distributed_pytorch_tpu.serving.mods import Mods, ModState
from distributed_pytorch_tpu.serving.scheduler import (
    Request,
    SamplingParams,
)

SNAPSHOT_VERSION = 1


class SnapshotUnavailable(RuntimeError):
    """No snapshot appeared under the polled key within the deadline.

    Raised only by the bounded-poll mode of :func:`adopt_snapshot` /
    :func:`fetch_snapshot_text` (``timeout_s`` set): the fail-fast mode
    keeps returning ``[]`` / ``None`` so existing probe-style callers
    ("adopt if a peer left something") stay cheap and exception-free."""


@dataclasses.dataclass(frozen=True)
class RequestSnapshot:
    """One admitted-but-unfinished request, as the codec persists it.

    ``generated`` holds only COMMITTED tokens (readback landed); the
    restored engine regenerates anything that was in flight. ``age_s`` is
    elapsed time since submission at snapshot — restore rebases
    ``submit_time`` so deadlines keep counting across the migration —
    and ``ttft_s`` the first-token latency if one was emitted (restored
    for e2e-latency continuity). ``kv_committed`` / ``trie_keys`` are the
    KV metadata: how many tokens had device K/V and the content-addressed
    prefix-trie chain covering them (see ``PrefixCache.key_chain``), so a
    restore target can predict its re-prefill bill without any device
    state crossing the wire."""

    req_id: int
    prompt: Tuple[int, ...]
    generated: Tuple[int, ...]
    max_new_tokens: int
    temperature: float
    seed: int
    stop_token: Optional[int]
    deadline_s: Optional[float]
    metadata: Optional[dict]
    preempt_count: int
    age_s: float
    ttft_s: Optional[float]
    kv_committed: int
    trie_keys: Tuple[str, ...]
    # Defaulted-last for wire compatibility (snapshots written before the
    # front door existed decode as anonymous, nothing-delivered, modless).
    # ``tenant_id`` preserves tenancy across drain/restore and failover;
    # ``delivered`` is the streaming high-water mark (tokens the client
    # already consumed) so a resumed stream neither replays nor skips;
    # ``stop_sequences``/``mods`` rebuild SamplingParams and the live
    # ModState (grammar DFAs re-walk ``generated`` — pure, so the state
    # lands exactly where it was).
    tenant_id: str = "anon"
    delivered: int = 0
    stop_sequences: Tuple[Tuple[int, ...], ...] = ()
    mods: Optional[dict] = None
    # Fleet-wide trace identity: survives drain hand-off and failover
    # id-rebasing (req_ids are engine-local; this string is not).
    # Defaulted so snapshots written before distributed tracing decode.
    trace_id: Optional[str] = None
    # Content-addressed keys of the pages HOST-resident in the source
    # engine's hostkv tier beyond the device chain (``trie_keys``
    # continues into ``host_keys``). Purely informational to the codec —
    # an adopter whose own host tier holds these keys recovers the
    # request by h2d fetch instead of re-prefill (the scheduler's
    # admission-time host continuation does the matching) — but it lets
    # a restore target predict its fetch-vs-reprefill bill up front.
    # Defaulted so snapshots written before the host tier decode.
    host_keys: Tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class EngineSnapshot:
    """A drained (or rolling) engine snapshot: every live request plus the
    engine fingerprint needed to validate a restore target. ``top_k`` /
    ``top_p`` are compiled into the decode program — restoring onto an
    engine with different truncation would silently change sampled
    outputs, so :func:`restore_engine` refuses. ``next_id`` preserves the
    id space: request ids ARE priorities, and a restored engine must not
    mint an id that outranks a recovered request. ``mesh`` is the
    ``"DxM"`` geometry fingerprint (``"1x1"`` unsharded) — same refusal
    logic: shards reorder float accumulation, so a sampled stream
    recovered onto different geometry could silently diverge."""

    version: int
    page_size: int
    max_seq_len: int
    top_k: int
    top_p: float
    speculative: bool
    next_id: int
    requests: Tuple[RequestSnapshot, ...]
    # Defaulted-last for wire compatibility: version-1 snapshots written
    # before mesh sharding existed decode as unsharded.
    mesh: str = "1x1"
    # KV-page dtype fingerprint ("fp" | "int8"): int8 pages round every
    # written K/V through quantization, so a request recovered across the
    # boundary would re-prefill into a numerically different cache and
    # sampled streams could silently diverge — same refusal logic as
    # ``mesh``. Defaulted so snapshots written before KV quantization
    # decode as fp.
    kv: str = "fp"

    # --------------------------------------------------------------- codec

    def to_json(self) -> str:
        doc = dataclasses.asdict(self)
        return json.dumps(doc, separators=(",", ":"), sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "EngineSnapshot":
        doc = json.loads(text)
        if doc.get("version") != SNAPSHOT_VERSION:
            raise ValueError(
                f"snapshot version {doc.get('version')!r} != "
                f"{SNAPSHOT_VERSION}"
            )
        doc.setdefault("mesh", "1x1")
        doc.setdefault("kv", "fp")
        reqs = []
        for entry in doc["requests"]:
            entry = dict(entry)
            entry["prompt"] = tuple(entry["prompt"])
            entry["generated"] = tuple(entry["generated"])
            entry["trie_keys"] = tuple(entry["trie_keys"])
            entry["host_keys"] = tuple(entry.get("host_keys", ()))
            entry["stop_sequences"] = tuple(
                tuple(int(t) for t in seq)
                for seq in entry.get("stop_sequences", ())
            )
            reqs.append(RequestSnapshot(**entry))
        doc["requests"] = tuple(reqs)
        return cls(**doc)

    def save(self, path: str) -> str:
        """Atomic write (tmp + rename), then the chaos hook — a
        ``corrupt_snapshot`` fault in an armed plan damages engine
        snapshots exactly as it does training checkpoints."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.to_json())
        os.replace(tmp, path)
        chaos.on_snapshot_write(path)
        return path

    @classmethod
    def load(cls, path: str) -> "EngineSnapshot":
        with open(path) as f:
            return cls.from_json(f.read())


# ----------------------------------------------------- sampling-params codec


def params_to_doc(params: SamplingParams) -> dict:
    """Canonical JSON-able form of :class:`SamplingParams` — ONE codec for
    every place a request's sampling config crosses a process or crash
    boundary (the replica control plane's ``/submit`` body, the router's
    write-ahead journal). JSON round-trips tuples as lists, so
    ``stop_sequences`` is listified here and re-tupled by
    :func:`params_from_doc`; keeping both directions side by side is what
    stops the wire format and the journal format from drifting apart."""
    doc = dataclasses.asdict(params)
    doc["stop_sequences"] = [
        [int(t) for t in seq] for seq in params.stop_sequences
    ]
    return doc


def params_from_doc(doc: Optional[dict]) -> SamplingParams:
    """Inverse of :func:`params_to_doc`. Tolerates a doc that came through
    JSON (lists re-tuple) and one written by an older incarnation (missing
    keys take the dataclass defaults)."""
    pdoc = dict(doc or {})
    pdoc["stop_sequences"] = tuple(
        tuple(int(t) for t in seq)
        for seq in pdoc.get("stop_sequences", ())
    )
    return SamplingParams(**pdoc)


# ----------------------------------------------------------------- snapshot


def snapshot_engine(engine) -> EngineSnapshot:
    """Codec every live (admitted, non-terminal) request of ``engine``.

    Read-only: nothing in the engine is mutated, so this serves both the
    clean drain (post ``finish_inflight``, no pending anywhere) and the
    ROLLING snapshot an overlapped engine writes between steps — there,
    tokens still awaiting readback are rolled back in the *copied* data
    (truncated at the oldest PENDING position); the restored engine
    re-issues those dispatches at the same fold indices and samples the
    identical values."""
    now = time.perf_counter()
    recs: List[RequestSnapshot] = []
    live = sorted(
        (r for r in engine.requests.values() if not r.done),
        key=lambda r: r.req_id,
    )
    for req in live:
        tokens = req.tokens
        if req.pending_idx:
            tokens = tokens[: req.pending_idx[0]]
        generated = tokens[len(req.prompt):]
        assert generated == req.generated[: len(generated)], (
            f"request {req.req_id}: committed tokens out of sync"
        )
        kv_committed = 0
        trie_keys: Tuple[str, ...] = ()
        host_keys: Tuple[str, ...] = ()
        if req.slot is not None:
            kv_committed = min(req.len_cached, len(tokens))
        if engine.prefix_cache is not None:
            device_keys, beyond = engine.prefix_cache.key_chain_tiered(
                tokens
            )
            trie_keys = tuple(device_keys)
            host_keys = tuple(beyond)
        recs.append(
            RequestSnapshot(
                req_id=req.req_id,
                prompt=tuple(req.prompt),
                generated=tuple(generated),
                max_new_tokens=req.params.max_new_tokens,
                temperature=req.params.temperature,
                seed=req.params.seed,
                stop_token=req.params.stop_token,
                deadline_s=req.params.deadline_s,
                metadata=req.metadata,
                preempt_count=req.preempt_count,
                age_s=max(0.0, now - req.submit_time),
                ttft_s=(
                    req.first_token_time - req.submit_time
                    if req.first_token_time is not None
                    else None
                ),
                kv_committed=kv_committed,
                trie_keys=trie_keys,
                host_keys=host_keys,
                tenant_id=req.tenant_id,
                # Delivery can never outrun commitment: the stream hands
                # out ``generated`` entries, and those are committed.
                delivered=min(req.delivered, len(generated)),
                stop_sequences=tuple(
                    tuple(int(t) for t in seq)
                    for seq in req.params.stop_sequences
                ),
                mods=(
                    req.mods.mods.to_spec() if req.mods is not None
                    else None
                ),
                trace_id=req.trace_id,
            )
        )
    return EngineSnapshot(
        version=SNAPSHOT_VERSION,
        page_size=engine.page_size,
        max_seq_len=engine.max_seq_len,
        top_k=engine._top_k,
        top_p=engine._top_p,
        speculative=engine.speculative,
        next_id=engine._next_id,
        requests=tuple(recs),
        mesh=engine.mesh_fingerprint,
        kv=engine.kv_fingerprint,
    )


def drain_engine(engine, reason: str = "drain") -> EngineSnapshot:
    """The SIGTERM-with-notice protocol, serving half: close the front door
    (submit -> :class:`~.admission.EngineDraining`), let the in-flight
    overlapped step land — one readback, no new dispatch, so whatever it
    finished is delivered rather than re-generated — then snapshot every
    still-live request."""
    engine.stop_admission()
    engine.finish_inflight()
    snap = snapshot_engine(engine)
    engine.drains += 1
    if engine.tracer.enabled:
        engine.tracer.instant(
            "drain", reason=reason, requests=len(snap.requests)
        )
    if engine.flight.enabled:
        engine.flight.record(
            "drain", reason=reason, requests=len(snap.requests)
        )
        engine._dump_postmortem(f"drain:{reason}")
    if engine.goodput is not None:
        # In-process downtime clock: closed again by restore_engine when
        # the same tracker survives (an in-process drain/restore cycle).
        engine.goodput.note_drain()
    return snap


# ------------------------------------------------------------------ restore


def restore_engine(
    engine, snapshot: EngineSnapshot, *, rebase_ids: bool = False
) -> List[int]:
    """Re-admit every snapshotted request into a fresh ``engine``,
    preserving ids (= priorities), sampling state, deadline clocks, and
    tenant metadata. Each request enters WAITING with
    ``tokens = prompt + generated``; the normal admission path then
    re-prefills through the prefix cache — exactly the preemption-resume
    machinery, so restored output is token-identical to an uninterrupted
    run. Returns the restored ids, oldest first.

    ``rebase_ids=True`` mints FRESH ids from the target's counter instead
    of preserving snapshot ids — the failover path for adopting several
    replicas' snapshots into one survivor, where two engines that counted
    ids from the same base would otherwise collide (preserving mode
    refuses such a duplicate with ``ValueError``). Snapshot order (oldest
    first) maps positionally onto the returned ids, so a router tracking
    shadow state can re-key its table; relative priority WITHIN the
    snapshot is preserved, but adopted requests rank behind the
    survivor's existing ones (fresh ids are higher = younger). Token
    streams are unaffected: sampling is keyed by per-request ``seed`` and
    fold index, never by req_id."""
    if snapshot.version != SNAPSHOT_VERSION:
        raise ValueError(
            f"snapshot version {snapshot.version} != {SNAPSHOT_VERSION}"
        )
    if getattr(engine, "state_layers", 0):
        raise ValueError(
            "an engine whose model has recurrent layers cannot restore a "
            "snapshot yet: a snapshot holds tokens and page geometry, and "
            "nothing of the per-slot recurrent state or of how it is rebuilt"
        )
    if (snapshot.top_k, snapshot.top_p) != (engine._top_k, engine._top_p):
        raise ValueError(
            f"snapshot was taken under top_k={snapshot.top_k} "
            f"top_p={snapshot.top_p}, engine compiled with "
            f"top_k={engine._top_k} top_p={engine._top_p} — sampled "
            "streams would diverge; restore onto a matching engine"
        )
    if snapshot.mesh != engine.mesh_fingerprint:
        raise ValueError(
            f"snapshot was taken on a {snapshot.mesh} mesh, restore "
            f"target is {engine.mesh_fingerprint} — sharded reductions "
            "reorder float accumulation, so recovered sampled streams "
            "could silently diverge; restore onto matching geometry"
        )
    if snapshot.kv != engine.kv_fingerprint:
        raise ValueError(
            f"snapshot was taken with {snapshot.kv} KV pages, restore "
            f"target uses {engine.kv_fingerprint} — int8 pages quantize "
            "every written K/V, so a request re-prefilled across the "
            "boundary could silently diverge; restore onto a matching "
            "KV configuration"
        )
    now = time.perf_counter()
    restored: List[int] = []
    tr = engine.tracer
    with tr.phase("restore"):
        for rec in snapshot.requests:
            if rebase_ids:
                req_id = engine._next_id
                engine._next_id += 1
            else:
                req_id = rec.req_id
                if req_id in engine.requests:
                    raise ValueError(
                        f"request id {req_id} already exists in the "
                        "restoring engine (restore with rebase_ids=True "
                        "to mint fresh ids on adoption)"
                    )
            total = len(rec.prompt) + rec.max_new_tokens
            if total > engine.max_seq_len:
                raise ValueError(
                    f"request {rec.req_id} needs {total} tokens; restore "
                    f"target caps at {engine.max_seq_len}"
                )
            params = SamplingParams(
                max_new_tokens=rec.max_new_tokens,
                temperature=rec.temperature,
                seed=rec.seed,
                stop_token=rec.stop_token,
                deadline_s=rec.deadline_s,
                stop_sequences=tuple(
                    tuple(int(t) for t in seq)
                    for seq in rec.stop_sequences
                ),
            )
            mod_state = None
            if rec.mods:
                mod_state = ModState(
                    Mods.from_spec(rec.mods), engine.vocab_size
                )
                # The DFA is pure: re-walking the committed tokens lands
                # the grammar state exactly where the dead engine left it.
                mod_state.replay(rec.generated)
            req = Request(
                req_id=req_id,
                prompt=list(rec.prompt),
                params=params,
                tokens=list(rec.prompt) + list(rec.generated),
                generated=list(rec.generated),
                submit_time=now - rec.age_s,
                preempt_count=rec.preempt_count,
                metadata=(
                    dict(rec.metadata) if rec.metadata is not None else None
                ),
                tenant_id=rec.tenant_id,
                delivered=rec.delivered,
                mods=mod_state,
                trace_id=rec.trace_id,
            )
            if rec.ttft_s is not None:
                req.first_token_time = req.submit_time + rec.ttft_s
            # Goodput: positions the dead engine had K/V for must be
            # re-prefilled here — charge them to restore_reprefill. A
            # prefix-cache re-match on re-admission shrinks the charge,
            # and when the snapshot's key_chain pages are host-resident
            # in the adopter, the host-tier fetch in _admit recovers
            # them without prefill at all.
            req.rework_until = rec.kv_committed
            req.rework_kind = "restore_reprefill"
            engine.requests[req_id] = req
            engine._keys[req_id] = host_prng_key(params.seed)
            engine.scheduler.add(req)
            if tr.enabled:
                extra = (
                    {"trace_id": rec.trace_id}
                    if rec.trace_id is not None else {}
                )
                tr.request_begin(
                    req_id,
                    prompt_len=len(rec.prompt),
                    max_new_tokens=rec.max_new_tokens,
                    restored=True,
                    recovered_tokens=len(rec.generated),
                    **extra,
                )
                if rec.trace_id is not None:
                    # The survivor picks up the fleet flow arrow: the
                    # restored span joins the victim's trace_id even
                    # though its req_id was rebased.
                    tr.flow("t", rec.trace_id, _PID_REQUESTS)
            restored.append(req_id)
    if not rebase_ids:
        # Preserving mode keeps the id space: the target must not mint an
        # id that outranks a recovered request. Rebasing already advanced
        # the counter past every minted id.
        engine._next_id = max(engine._next_id, snapshot.next_id)
    engine.restores += 1
    engine.requests_recovered += len(restored)
    if tr.enabled:
        tr.instant("restore", requests=len(restored))
    if engine.flight.enabled:
        engine.flight.record("restore", requests=len(restored))
    if engine.goodput is not None:
        engine.goodput.note_restore()
    return restored


# --------------------------------------------------------- drain controller


class DrainController:
    """Wires reclaim notices into an engine's step loop.

    ``install_signal=True`` registers a SIGTERM handler that merely sets a
    flag — everything observable happens between steps, inside
    :meth:`drive`: on notice, the engine drains (admission closed,
    in-flight step finished, snapshot written) and ``drive`` returns early.
    ``snapshot_every=N`` additionally writes a ROLLING snapshot to
    ``snapshot_path`` every N steps, the recovery point for faults with no
    notice at all (SIGKILL, ``kill_mid_verify``). Usable as a context
    manager to restore the previous signal handler on exit."""

    def __init__(
        self,
        engine,
        *,
        snapshot_path: Optional[str] = None,
        install_signal: bool = False,
        signum: int = signal.SIGTERM,
    ):
        self.engine = engine
        self.snapshot_path = snapshot_path
        self.drain_requested = False
        self.drained = False
        self.snapshot: Optional[EngineSnapshot] = None
        self._signum = signum
        self._prev_handler = None
        if install_signal:
            self._prev_handler = signal.signal(signum, self._on_signal)

    def _on_signal(self, signum, frame) -> None:
        self.request_drain()

    def request_drain(self) -> None:
        self.drain_requested = True

    def uninstall(self) -> None:
        if self._prev_handler is not None:
            signal.signal(self._signum, self._prev_handler)
            self._prev_handler = None

    def __enter__(self) -> "DrainController":
        return self

    def __exit__(self, *exc) -> bool:
        self.uninstall()
        return False

    def _write(self, snap: EngineSnapshot) -> None:
        self.snapshot = snap
        if self.snapshot_path is not None:
            snap.save(self.snapshot_path)

    def drain_now(self) -> EngineSnapshot:
        """Drain immediately (between steps) and record the snapshot."""
        snap = drain_engine(self.engine)
        self._write(snap)
        self.drained = True
        return snap

    def drive(
        self, max_steps: int = 10_000, snapshot_every: Optional[int] = None
    ) -> List[int]:
        """``engine.run()`` with the elastic hooks: checks the drain flag
        between steps (a notice mid-step drains after that step's device
        work lands) and writes rolling snapshots every ``snapshot_every``
        steps. Returns the ids finished before completion or drain."""
        eng = self.engine
        finished: List[int] = []
        steps = 0
        try:
            while eng.scheduler.has_work or eng._inflight is not None:
                if self.drain_requested:
                    self.drain_now()
                    return finished
                if steps >= max_steps:
                    raise RuntimeError(
                        f"engine did not drain within {max_steps} steps"
                    )
                finished.extend(eng.step())
                steps += 1
                if snapshot_every and steps % snapshot_every == 0:
                    self._write(snapshot_engine(eng))
        except BaseException as exc:
            # Same last-gasp postmortem as InferenceEngine.run(): crashes
            # escaping the drive loop leave a dump + trace behind.
            flush = getattr(eng, "_flush_on_crash", None)
            if flush is not None:
                flush("exception", exc)
            raise
        if self.drain_requested and not self.drained:
            # Notice arrived as the queue emptied: drain the (now idle)
            # engine so the caller still gets its snapshot + closed door.
            self.drain_now()
        return finished


# ------------------------------------------------------------ peer handoff


def publish_snapshot(store, key: str, snapshot: EngineSnapshot) -> None:
    """Hand a drained engine's queue to peers via the elastic KV store
    (:class:`~distributed_pytorch_tpu.elastic.store.KVStoreClient`)."""
    store.set(key, snapshot.to_json())


def fetch_snapshot_text(
    store, key: str, *, timeout_s: float,
    poll_interval_s: float = 0.02,
) -> str:
    """Poll ``store`` for ``key`` until it appears or ``timeout_s``
    elapses, sleeping a jittered exponential backoff between probes
    (capped at 0.25s so a snapshot published late in the window is still
    picked up promptly). The race this covers: a dying replica's final
    ``publish_snapshot`` can lose to the survivor's adoption attempt by
    milliseconds, and failing fast there turns a clean hand-off into an
    avoidable re-generation. Raises :class:`SnapshotUnavailable` on
    deadline."""
    import random

    deadline = time.monotonic() + max(0.0, timeout_s)
    sleep_s = max(1e-4, poll_interval_s)
    while True:
        text = store.get(key)
        if text is not None:
            return text
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise SnapshotUnavailable(
                f"no snapshot under {key!r} after {timeout_s:.3f}s"
            )
        # Full jitter on the backoff: many survivors polling one store for
        # one victim's key should not probe in lockstep.
        time.sleep(min(remaining, sleep_s * (0.5 + random.random() * 0.5)))
        sleep_s = min(sleep_s * 2.0, 0.25)


def adopt_snapshot(
    engine, store, key: str, *, delete: bool = True,
    rebase_ids: bool = False, timeout_s: Optional[float] = None,
) -> List[int]:
    """Fetch a published snapshot and restore it into ``engine``; deletes
    the key afterwards by default (adopt-once). Returns the restored ids,
    or ``[]`` when no snapshot is published under ``key``.
    ``rebase_ids=True`` mints fresh ids on adoption (see
    :func:`restore_engine`) — required when one survivor adopts snapshots
    from several peers whose id spaces overlap.

    ``timeout_s`` switches from fail-fast to a bounded poll with jittered
    backoff (see :func:`fetch_snapshot_text`): the adopter waits that long
    for a not-yet-published key before raising
    :class:`SnapshotUnavailable` — covering a publisher whose final write
    races its own death."""
    if timeout_s is None:
        text = store.get(key)
        if text is None:
            return []
    else:
        text = fetch_snapshot_text(store, key, timeout_s=timeout_s)
    ids = restore_engine(
        engine, EngineSnapshot.from_json(text), rebase_ids=rebase_ids
    )
    if delete:
        store.delete(key)
    return ids
