"""Request lifecycle tracer + engine and trainer step timeline,
Perfetto-exportable.

Four timelines, one clock:

* **Request spans** — one async span per accepted request, opened at
  ``submit`` and closed at retire, with instant events for every lifecycle
  transition in between: ``admit`` (slot, prefix-cache hit/miss, cached
  token count), each ``prefill_chunk``, every resolved ``decode_token`` /
  speculative ``verify_round`` (accepted-token counts), ``preempt``,
  ``cow_copy``, and page ``evict`` pressure.
* **Engine steps** — one duration slice per ``InferenceEngine.step()`` with
  nested phase slices (``schedule`` / ``cow`` / ``prefill`` / ``dispatch``
  / ``readback``) and per-step counter tracks (batch composition,
  token-budget utilization, pages free/referenced/cached-idle, queue
  depth). The phases that hold the host's time have children:
  ``dispatch.key`` (one slice a launch), ``dispatch.stage``,
  ``dispatch.launch``, ``readback.wait`` (the host blocked on the device),
  ``readback.resolve``, and one ``prefill.chunk`` a prefill piece (one
  program: ``tokens``, ``start``, and the ``width`` it was padded to).
* **Training steps** — a ``Trainer`` writes one ``epoch`` slice an epoch,
  inside it a ``step`` slice a batch holding ``put_batch`` and
  ``step.dispatch``, and ``epoch.loss_fetch`` where the host waits for the
  device at the epoch's end; its ``ShardedLoader`` writes ``loader.index``
  and ``loader.stack`` for every batch between the steps, and the Trainer
  ``recycle.fence`` after a step, where it waits for an earlier step to end
  before it hands that step's host arrays back to the loader. Both record to
  :func:`process_tracer` unless handed another.
* **Set-up** — what a process does before its first step, always in
  :func:`process_tracer` (set-up belongs to the process, not to a step or a
  request, and an engine's own tracer may be off or emptied), kept in
  ``Tracer.setup_events``, a list the ring does not reach, and written out
  first: ``process.start`` (from the process's start as the OS has it to
  the tracer's making: interpreter and imports; written by
  :func:`process_tracer`), ``backend.open`` (a backend's opening, with its
  ``platform``) and one ``compile`` a program JAX compiles (``fun_name``,
  the parts ``trace_s`` / ``lower_s`` / ``backend_s``, ``cache``
  ``hit`` / ``miss`` / ``off``, ``retrieval_s`` on a hit, ``written`` where
  the persistent cache got a new entry; both written by ``obs/xla.py``'s one
  ``jax.monitoring`` dispatcher, which ``utils/platform.py``
  ``enable_compile_cache()`` installs), ``engine.init`` > ``engine.init.pools``
  (``bytes``) and ``engine.build_prefill_programs`` (``programs``,
  ``widths``; ``serving/engine.py``), ``trainer.init``
  (``training/trainer.py``; the step's compile lies inside the first
  ``epoch`` > ``step`` slices by time containment).

Export is Chrome ``trace_event`` JSON (:meth:`Tracer.to_perfetto` /
:meth:`Tracer.save`) — load it at https://ui.perfetto.dev or
``chrome://tracing``. Request spans are async events keyed by request id,
so they line up under the engine-step track.

**The clock.** ``ts`` is microseconds of ``time.perf_counter`` counted from
the tracer's construction (the process's tracer: from the process's start,
so that set-up is one lane from ``ts`` 0 to the first step), so it is NOT
a ``jax.profiler`` trace's clock (that one counts nanoseconds from the
profiler's own start). Every slice
(``ph: X``) therefore carries the raw ``perf_counter`` nanoseconds of its
start as ``args["perf_counter_ns"]``: a holder of ``events`` alone places
any event on ``perf_counter`` with :func:`perf_counter_offset_us`, and
whoever holds a profiler trace of the same window maps ``perf_counter``
onto it from one instant known on both clocks (``benchmarks/harness/
phases.py`` does). ``wall_epoch_s`` anchors ``ts`` zero on the wall clock
for :func:`~.disttrace.merge_traces` across processes.

The disabled path is the null-object pattern: :data:`NULL_TRACER` is a
shared :class:`NullTracer` whose every method is a no-op ``pass`` and whose
``phase()`` returns a shared no-op context manager — no timestamps taken,
no dicts built, no branches in the caller beyond an attribute load. The
engine guards its per-step gauge *computation* behind ``tracer.enabled``
so a disabled engine does zero extra work; serving outputs are
bitwise-identical either way (pinned by tests).
"""

from __future__ import annotations

import collections
import hashlib
import json
import os
import time
from typing import Callable, Dict, Iterable, Optional

# Perfetto process lanes: engine steps/phases under pid 1, request spans
# under pid 2 — two top-level tracks that scroll together. The serving
# layers above the engine get lanes of their own: front-door streams under
# pid 3, router decisions under pid 4, so a merged fleet trace reads
# top-down in causal order (door → router → engine).
_PID_ENGINE = 1
_PID_REQUESTS = 2
_PID_DOOR = 3
_PID_ROUTER = 4

# The set-up slices' thread of the engine lane: one lane from the process's
# start to the first step.
_TID_SETUP = 2
#: How many set-up slices a tracer keeps out of its ring's reach.
SETUP_EVENTS_MAX = 4096

# Span category per lane — async events are matched by (cat, id), so the
# door's stream #7 and the engine's request #7 never collide.
_SPAN_CAT = {_PID_REQUESTS: "request", _PID_DOOR: "door", _PID_ROUTER: "router"}


def flow_id(trace_id: str) -> int:
    """Stable integer id for Perfetto flow arrows carrying one fleet-wide
    ``trace_id``. Flow events (``ph: s/t/f``) are matched by
    (name, cat, id); hashing the string identically in every process lets
    door, router, and replicas emit linked arrows without coordination.
    48 bits keeps the id an exact JSON double."""
    digest = hashlib.sha1(trace_id.encode("utf-8")).digest()
    return int.from_bytes(digest[:6], "big")


class _NullContext:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args) -> None:
        pass


_NULL_CONTEXT = _NullContext()


class NullTracer:
    """Every method a no-op; ``enabled`` False so callers can skip gauge
    computation entirely. One shared instance (:data:`NULL_TRACER`) serves
    every disabled engine."""

    __slots__ = ()
    enabled = False

    def begin_step(self) -> None:
        pass

    def end_step(self, **gauges) -> None:
        pass

    def phase(self, name: str, **args) -> _NullContext:
        return _NULL_CONTEXT

    def request_begin(self, req_id: int, **attrs) -> None:
        pass

    def request_event(self, req_id: int, name: str, **attrs) -> None:
        pass

    def request_end(self, req_id: int, **attrs) -> None:
        pass

    def instant(self, name: str, **attrs) -> None:
        pass

    def set_engine_label(self, label: str) -> None:
        pass

    def span_begin(self, pid: int, sid: int, name: str, **attrs) -> None:
        pass

    def span_event(self, pid: int, sid: int, name: str, **attrs) -> None:
        pass

    def span_end(self, pid: int, sid: int, name: str, **attrs) -> None:
        pass

    def flow(self, phase: str, trace_id: str, pid: int, tid: int = 0) -> None:
        pass


NULL_TRACER = NullTracer()


class _Phase:
    """Context manager emitting one ``X`` (complete) slice on the engine
    track; nested phases nest visually by time containment. ``args`` given
    at construction or through :meth:`note` (counts known only at the end)
    land in the slice's ``args`` beside the step index; ``seconds`` is the
    slice's duration once it has closed."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "seconds")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        self._t0 = self._tracer._clock()
        return self

    def note(self, **args) -> None:
        self._args.update(args)

    def __exit__(self, *exc):
        tr = self._tracer
        self.seconds = tr._clock() - self._t0
        tr.events.append(
            {
                "name": self._name,
                "cat": "engine",
                "ph": "X",
                "ts": (self._t0 - tr._epoch) * 1e6,
                "dur": self.seconds * 1e6,
                "pid": _PID_ENGINE,
                "tid": 0,
                "args": {
                    "step": tr.step_index,
                    "perf_counter_ns": int(self._t0 * 1e9),
                    **self._args,
                },
            }
        )
        return False


class _SetupPhase(_Phase):
    """:class:`_Phase` for a set-up slice: written through
    :meth:`Tracer.setup_slice` when it closes."""

    __slots__ = ()

    def __exit__(self, *exc):
        tr = self._tracer
        self.seconds = tr._clock() - self._t0
        tr.setup_slice(self._name, self._t0, self.seconds, **self._args)
        return False


class Tracer:
    """Recording tracer. Construct one and hand it to
    ``InferenceEngine(..., tracer=tracer)``; after the run,
    :meth:`save` writes a Perfetto-loadable JSON trace.

    Events accumulate in memory as ``trace_event`` dicts (microsecond
    timestamps relative to construction): without bound by default, or,
    with ``max_events``, in a ring that drops the oldest. ``spans_opened`` /
    ``spans_closed`` count request spans — a drained engine satisfies
    ``spans_closed == requests completed``.
    """

    enabled = True

    def __init__(
        self,
        clock: Callable[[], float] = time.perf_counter,
        wall_clock: Callable[[], float] = time.time,
        max_events: Optional[int] = None,
        epoch: Optional[float] = None,
    ):
        """``epoch``: where ``ts`` 0 lies on ``clock``, if not now (the
        process's tracer counts from the process's start)."""
        self._clock = clock
        now = clock()
        self._epoch = now if epoch is None else epoch
        # Wall-clock anchor for the monotonic epoch: ``ts`` microseconds
        # are relative to construction, so two independently-created
        # tracers (door, router, each replica) can only be merged onto one
        # timeline if each records WHEN its zero was. Exported in
        # :meth:`to_perfetto` metadata; `merge_traces` shifts by the epoch
        # deltas. Old saved traces without the field align at 0.0.
        self.wall_epoch_s: float = wall_clock() - (now - self._epoch)
        self.events = (
            [] if max_events is None
            else collections.deque(maxlen=max_events)
        )
        # Set-up slices (module docstring): a list of their own, which the
        # ring does not reach, written out before ``events``.
        self.setup_events: list = []
        self.step_index = -1
        self._step_t0 = self._epoch
        self.spans_opened = 0
        self.spans_closed = 0
        self.engine_label: str = ""

    def set_engine_label(self, label: str) -> None:
        """Annotate the engine process lane (e.g. ``"mesh 2x4"``) — shows
        up in the Perfetto process name so traces from differently-sharded
        engines are tellable apart at a glance. Unset keeps the historical
        plain ``engine`` name byte-for-byte."""
        self.engine_label = str(label)

    def _now_us(self) -> float:
        return (self._clock() - self._epoch) * 1e6

    # -------------------------------------------------------- engine steps

    def begin_step(self) -> None:
        self.step_index += 1
        self._step_t0 = self._clock()

    def end_step(self, **gauges) -> None:
        """Close the current step slice and sample every gauge onto its own
        counter track (``ph: C``) at the step boundary."""
        ts = (self._step_t0 - self._epoch) * 1e6
        now = self._now_us()
        self.events.append(
            {
                "name": "step",
                "cat": "engine",
                "ph": "X",
                "ts": ts,
                "dur": now - ts,
                "pid": _PID_ENGINE,
                "tid": 1,
                "args": {
                    "step": self.step_index,
                    "perf_counter_ns": int(self._step_t0 * 1e9),
                    **gauges,
                },
            }
        )
        for name, value in gauges.items():
            self.events.append(
                {
                    "name": name,
                    "cat": "gauge",
                    "ph": "C",
                    "ts": now,
                    "pid": _PID_ENGINE,
                    "args": {"value": value},
                }
            )

    def phase(self, name: str, **args) -> _Phase:
        return _Phase(self, name, args)

    # -------------------------------------------------------------- set-up

    def setup_phase(self, name: str, **args) -> _Phase:
        """:meth:`phase` for a set-up slice (module docstring)."""
        return _SetupPhase(self, name, args)

    def setup_slice(
        self, name: str, start_s: float, seconds: float, **args
    ) -> None:
        """A set-up slice whose times are known only afterwards: ``start_s``
        on the tracer's clock (``perf_counter``), ``seconds`` long. Kept in
        ``setup_events`` while that is short: a process that compiles
        without end (a program a request's length) must not grow it without
        bound, so past ``SETUP_EVENTS_MAX`` the slices go to the ring."""
        kept = len(self.setup_events) < SETUP_EVENTS_MAX
        (self.setup_events if kept else self.events).append(
            {
                "name": name,
                "cat": "setup",
                "ph": "X",
                "ts": (start_s - self._epoch) * 1e6,
                "dur": seconds * 1e6,
                "pid": _PID_ENGINE,
                "tid": _TID_SETUP,
                "args": {"perf_counter_ns": int(start_s * 1e9), **args},
            }
        )

    # ------------------------------------------------------- request spans

    def request_begin(self, req_id: int, **attrs) -> None:
        self.spans_opened += 1
        self.events.append(
            {
                "name": "request",
                "cat": "request",
                "ph": "b",
                "id": int(req_id),
                "ts": self._now_us(),
                "pid": _PID_REQUESTS,
                "tid": 0,
                "args": {"req_id": int(req_id), **attrs},
            }
        )

    def request_event(self, req_id: int, name: str, **attrs) -> None:
        self.events.append(
            {
                "name": name,
                "cat": "request",
                "ph": "n",
                "id": int(req_id),
                "ts": self._now_us(),
                "pid": _PID_REQUESTS,
                "tid": 0,
                "args": attrs,
            }
        )

    def request_end(self, req_id: int, **attrs) -> None:
        self.spans_closed += 1
        self.events.append(
            {
                "name": "request",
                "cat": "request",
                "ph": "e",
                "id": int(req_id),
                "ts": self._now_us(),
                "pid": _PID_REQUESTS,
                "tid": 0,
                "args": attrs,
            }
        )

    def instant(self, name: str, pid: int = _PID_ENGINE, **attrs) -> None:
        """Global instant event (page evictions, chaos marks, door
        backpressure windows — ``pid`` picks the lane)."""
        self.events.append(
            {
                "name": name,
                "cat": "engine",
                "ph": "i",
                "s": "g",
                "ts": self._now_us(),
                "pid": pid,
                "tid": 0,
                "args": attrs,
            }
        )

    # ------------------------------------------- door / router span lanes

    def span_begin(self, pid: int, sid: int, name: str, **attrs) -> None:
        """Open an async span on a serving-layer lane (``_PID_DOOR`` /
        ``_PID_ROUTER``). ``sid`` keys the span within its lane's category
        — door stream sequence numbers, router fleet ids — so it can never
        collide with engine req_ids (different ``cat``)."""
        self.spans_opened += 1
        self.events.append(
            {
                "name": name,
                "cat": _SPAN_CAT.get(pid, "request"),
                "ph": "b",
                "id": int(sid),
                "ts": self._now_us(),
                "pid": pid,
                "tid": 0,
                "args": attrs,
            }
        )

    def span_event(self, pid: int, sid: int, name: str, **attrs) -> None:
        self.events.append(
            {
                "name": name,
                "cat": _SPAN_CAT.get(pid, "request"),
                "ph": "n",
                "id": int(sid),
                "ts": self._now_us(),
                "pid": pid,
                "tid": 0,
                "args": attrs,
            }
        )

    def span_end(self, pid: int, sid: int, name: str, **attrs) -> None:
        self.spans_closed += 1
        self.events.append(
            {
                "name": name,
                "cat": _SPAN_CAT.get(pid, "request"),
                "ph": "e",
                "id": int(sid),
                "ts": self._now_us(),
                "pid": pid,
                "tid": 0,
                "args": attrs,
            }
        )

    def flow(self, phase: str, trace_id: str, pid: int, tid: int = 0) -> None:
        """One hop of the fleet-wide flow arrow for ``trace_id``.

        ``phase`` is ``"s"`` where the id is MINTED (door admission, or a
        bare router submit), ``"t"`` at every downstream hop (router route,
        engine admission, failover re-admission on the survivor), ``"f"``
        to terminate. All emitters hash the same string to the same 48-bit
        flow id, so the merged trace draws door → router → replica arrows
        without any cross-process coordination."""
        event = {
            "name": "trace",
            "cat": "flow",
            "ph": phase,
            "id": flow_id(trace_id),
            "ts": self._now_us(),
            "pid": pid,
            "tid": tid,
            "args": {"trace_id": trace_id},
        }
        if phase == "t":
            # Bind incoming arrows at the enclosing slice's start so the
            # arrowhead lands on the span, not after it.
            event["bp"] = "e"
        self.events.append(event)

    # -------------------------------------------------------------- export

    def to_perfetto(self) -> Dict[str, object]:
        """Chrome ``trace_event`` document: recorded events plus process /
        thread name metadata so the lanes are labeled in the UI."""
        engine_name = (
            f"engine [{self.engine_label}]" if self.engine_label
            else "engine"
        )
        meta = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": _PID_ENGINE,
                "args": {"name": engine_name},
            },
            {
                "name": "thread_name",
                "ph": "M",
                "pid": _PID_ENGINE,
                "tid": 0,
                "args": {"name": "step phases"},
            },
            {
                "name": "thread_name",
                "ph": "M",
                "pid": _PID_ENGINE,
                "tid": 1,
                "args": {"name": "steps"},
            },
            {
                "name": "process_name",
                "ph": "M",
                "pid": _PID_REQUESTS,
                "args": {"name": "requests"},
            },
        ]
        if self.setup_events:
            meta.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": _PID_ENGINE,
                    "tid": _TID_SETUP,
                    "args": {"name": "set-up"},
                }
            )
        # Serving-layer lanes are labeled only when populated, so an
        # engine-only trace keeps its historical two-process shape.
        used_pids = {e.get("pid") for e in self.events}
        for pid, label in ((_PID_DOOR, "front door"), (_PID_ROUTER, "router")):
            if pid in used_pids:
                meta.append(
                    {
                        "name": "process_name",
                        "ph": "M",
                        "pid": pid,
                        "args": {"name": label},
                    }
                )
        return {
            "traceEvents": meta + self.setup_events + list(self.events),
            "displayTimeUnit": "ms",
            # Clock anchor for multi-tracer assembly (see `merge_traces`):
            # seconds-since-Unix-epoch at which this tracer's ts=0 was.
            "metadata": {"wall_epoch_s": self.wall_epoch_s},
        }

    def save(self, path: str) -> str:
        """Write the Perfetto JSON trace to ``path``; returns the path."""
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_perfetto(), f)
        return path


def perf_counter_offset_us(events: Iterable[dict]) -> Optional[float]:
    """What to add to an event's ``ts`` to get ``time.perf_counter()`` in
    microseconds, from the events alone: any slice carries both. ``None``
    where ``events`` hold no slice."""
    for event in events:
        start_ns = event.get("args", {}).get("perf_counter_ns")
        if start_ns is not None and "ts" in event:
            return start_ns / 1e3 - event["ts"]
    return None


PROCESS_TRACER_EVENTS = 65_536
_process_tracer: Optional[Tracer] = None
_IMPORTED_AT = time.perf_counter()


def process_start() -> tuple:
    """``(perf_counter seconds, source)`` of this process's start: from the
    OS (``/proc/self/stat``'s start time, in clock ticks since boot, against
    ``CLOCK_BOOTTIME``: to a tick, 10 ms) where it says, else this module's
    import (``source`` ``"import"``), which leaves the interpreter's own
    start and the imports before this one out."""
    try:
        with open("/proc/self/stat") as f:
            # The 22nd field; the 2nd, the command, may hold spaces.
            ticks = int(f.read().rpartition(")")[2].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf(
            "SC_CLK_TCK"
        )
        now = time.perf_counter()
    except (OSError, ValueError, IndexError, AttributeError):
        return _IMPORTED_AT, "import"
    if age < 0.0 or now - age > _IMPORTED_AT:
        return _IMPORTED_AT, "import"  # a clock the OS does not keep
    return now - age, "proc_stat"


def process_tracer() -> Tracer:
    """The process's one bounded :class:`Tracer`, made on first use: what
    a ``Trainer`` and a ``ShardedLoader`` record to unless handed another,
    and where set-up is written (module docstring). Its ring holds the last
    ``PROCESS_TRACER_EVENTS`` events (a training step writes five), so it
    costs a fixed few tens of MB however long the process runs;
    ``process_tracer().save(path)`` writes set-up and the last steps'
    phases out. Its ``ts`` counts from the process's start, which the
    ``process.start`` slice, the first it keeps, marks on ``perf_counter``."""
    global _process_tracer
    if _process_tracer is None:
        started, source = process_start()
        tracer = Tracer(max_events=PROCESS_TRACER_EVENTS, epoch=started)
        tracer.setup_slice(
            "process.start", started, time.perf_counter() - started,
            source=source,
        )
        _process_tracer = tracer
    return _process_tracer
