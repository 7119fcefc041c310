"""Device-truth accounting: compiled-program ledger + recompile sentinel.

Everything else in ``obs/`` measures the host's view — wall-clock phases,
queue depths, reservoir latencies. This module measures what XLA is
actually doing with the device:

* :class:`ProgramLedger` wraps each of the engine's compiled programs.
  On the first call with a new argument signature (shapes/dtypes of the
  flattened args) it runs an ANALYSIS-ONLY ahead-of-time compile —
  ``fn.lower(*args).compile()`` — and records compile wall time,
  ``memory_analysis()`` HBM breakdown (argument / output / temp /
  generated-code bytes) and ``cost_analysis()`` FLOPs per program. The
  analyzed executable is then dropped: execution always goes through the
  original jitted callable, so ledger-on output is bitwise-identical to
  ledger-off by construction (the ledger pays one extra compile per
  signature, never a different program). The ledger also carries the
  host↔device transfer counters (staging bytes up, readback bytes down)
  that the engine feeds per step, and a live-buffer HBM watermark read
  from ``jax.live_arrays()``.

* :class:`RecompileSentinel` — after warmup, any new XLA compilation is
  a silent perf killer (a stray shape reaching the step fn recompiles a
  multi-second program mid-serve). Once :meth:`~RecompileSentinel.arm`\\ ed,
  the sentinel trips on (a) any ledger signature miss — with the program
  name and offending shapes — and (b) any backend-compile event from
  ``jax.monitoring`` that is NOT attributed to a ledgered compile, which
  catches compilations the ledger never saw and names them: the trip
  carries JAX's own name of the program and its ``compile`` slice's parts.
  Each trip bumps a counter (exported as ``engine_recompiles_total``),
  records a flight-recorder event, drops a tracer instant, and latches an
  SLO-style firing gauge.

* **The set-up timeline.** This module holds the process's ONE
  ``jax.monitoring`` dispatcher (:func:`install_dispatcher`, which
  ``utils/platform.py`` ``enable_compile_cache()`` calls before an entry
  point's first compile, and ``RecompileSentinel.arm()`` again). From then
  on every program JAX compiles writes one ``compile`` slice into
  :func:`~.tracer.process_tracer`'s set-up list, and every backend JAX
  opens a ``backend.open`` slice (:class:`_CompileTimeline`,
  :class:`_BackendOpenTap`). Nothing here runs unless JAX traces, lowers or
  compiles: a step that compiles nothing fires nothing.

The dispatcher fans the backend event out to a ``WeakSet`` of armed
sentinels — engines come and go, the listeners stay.
"""

from __future__ import annotations

import logging
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from distributed_pytorch_tpu.obs.tracer import process_tracer

# The jax.monitoring keys (jax/_src/dispatch.py, compiler.py,
# compilation_cache.py in JAX 0.9.0). The three durations carry ``fun_name``
# and fire on the compiling thread in this order; the backend event fires
# once a program that reaches XLA OR the persistent cache (a hit fires it
# too, with the time the load took), never for a jit call whose executable
# the process already holds.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# Fired where JAX WRITES an entry, not where it misses: a program under the
# cache's thresholds misses in every run and never fires it.
_CACHE_WRITTEN = "/jax/compilation_cache/cache_misses"

# ---------------------------------------------------------------------------
# Process-wide dispatcher (jax.monitoring offers global registration only).
# ---------------------------------------------------------------------------

_armed_sentinels: "weakref.WeakSet" = weakref.WeakSet()
_dispatcher_lock = threading.Lock()
_dispatcher_installed = False

# Compile events fire synchronously on the thread doing the compilation,
# so a thread-local attribution scope is race-free.
_attribution = threading.local()


def _current_attribution() -> Optional[Tuple[str, tuple]]:
    return getattr(_attribution, "scope", None)


class _CompileTimeline(threading.local):
    """One thread's compile in the making: the parts JAX has reported since
    the last ``compile`` slice, as ``(fun_name, start, seconds)`` on
    ``perf_counter`` (an event fires when its part ENDS, so its start is the
    arrival less the duration), and what the persistent cache said.

    A trace event fires for every jitted function traced: the ones inside
    another's trace before it (``jnp.add`` inside ``f`` inside ``step``),
    and the ones a LOWERING traces (the functions it lowers through
    ``jax``) before the lowering's own event. So the last trace of each
    name is kept, and a lowering ``jit(f)`` takes ``f``'s if that ended
    before the lowering began; a trace that no lowering follows
    (``jax.eval_shape``) is dropped at the next lowering.
    """

    lower: Optional[Tuple[str, float, float]] = None
    trace: Optional[Tuple[str, float, float]] = None
    cache = "off"
    retrieval_s: Optional[float] = None
    written = False

    def __init__(self) -> None:
        self.traces: Dict[str, Tuple[str, float, float]] = {}

    def reset(self) -> None:
        self.traces.clear()
        self.trace = self.lower = self.retrieval_s = None
        self.cache, self.written = "off", False

    def on_duration(self, event: str, seconds: float, fun_name: str) -> None:
        now = time.perf_counter()
        part = (fun_name, now - seconds, seconds)
        if event == _TRACE_EVENT:
            if len(self.traces) >= 256:  # names nobody lowered
                self.traces.clear()
            self.traces[fun_name] = part
        elif event == _LOWER_EVENT:
            # ``jit(f)``: the name of the function inside the API's
            traced = self.traces.get(fun_name[fun_name.find("(") + 1:-1])
            self.reset()
            self.lower = part
            if traced is not None and traced[1] + traced[2] <= part[1]:
                self.trace = traced
        elif event == _COMPILE_EVENT:
            self.finish(part, now)

    def finish(self, backend: Tuple[str, float, float], now: float) -> None:
        """The backend's part closes the program: write its slice, from the
        start of its first part to now, and tell the armed sentinels."""
        fun_name, start, backend_s = backend
        args = {"fun_name": fun_name, "trace_s": 0.0, "lower_s": 0.0,
                "backend_s": backend_s, "cache": self.cache}
        if self.lower is not None and self.lower[0] == fun_name:
            start, args["lower_s"] = self.lower[1], self.lower[2]
            if self.trace is not None:
                start, args["trace_s"] = self.trace[1], self.trace[2]
        if self.retrieval_s is not None:
            args["retrieval_s"] = self.retrieval_s
        if self.written:
            args["written"] = True
        self.reset()
        process_tracer().setup_slice("compile", start, now - start, **args)
        for sentinel in list(_armed_sentinels):
            sentinel._on_backend_compile(args)


_timeline = _CompileTimeline()


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    fun_name = kwargs.get("fun_name")
    if fun_name is not None:
        _timeline.on_duration(event, duration, fun_name)
    elif event == _CACHE_RETRIEVAL:
        _timeline.retrieval_s = duration


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_ASKED:
        # JAX "asks" a cache that has no directory too, and gets nothing.
        if jax.config.jax_compilation_cache_dir:
            _timeline.cache = "miss"  # until a hit says otherwise
    elif event == _CACHE_HIT:
        _timeline.cache = "hit"
    elif event == _CACHE_WRITTEN:
        _timeline.written = True


class _BackendOpenTap(logging.Filter):
    """Writes a ``backend.open`` slice for every backend JAX opens.

    JAX reports a backend's opening only as two DEBUG records of the logger
    ``jax._src.xla_bridge`` (``"Initializing backend '%s'"``, ``"Backend
    '%s' initialized"``). So the tap sets that logger to DEBUG and, as a
    filter ON the logger, reads the two records and then drops every record
    the logger would not have made at the level it would have without the
    tap (its own where it had one, else its parent's, read anew for every
    record): nothing reaches a handler that would not have, and the process
    prints what it would have printed.
    """

    LOGGER = "jax._src.xla_bridge"
    OPENING, OPENED = "Initializing backend '%s'", "Backend '%s' initialized"

    def __init__(self) -> None:
        super().__init__()
        self.logger = logging.getLogger(self.LOGGER)
        self.level_before = self.logger.level
        self.opening: Dict[str, float] = {}
        self.logger.addFilter(self)
        self.logger.setLevel(logging.DEBUG)

    def filter(self, record: logging.LogRecord) -> bool:
        if record.msg == self.OPENING and record.args:
            self.opening[str(record.args[0])] = time.perf_counter()
        elif record.msg == self.OPENED and record.args:
            platform = str(record.args[0])
            start = self.opening.pop(platform, None)
            if start is not None:
                process_tracer().setup_slice(
                    "backend.open", start, time.perf_counter() - start,
                    platform=platform,
                )
        if self.logger.level != logging.DEBUG:
            return True  # somebody set a level of their own since: theirs holds
        return record.levelno >= (
            self.level_before or self.logger.parent.getEffectiveLevel()
        )


_backend_tap: Optional[_BackendOpenTap] = None


def install_dispatcher() -> bool:
    """Install the process's one ``jax.monitoring`` dispatcher and the
    backend tap; once, however often it is called. The set-up timeline
    starts here: the process's tracer is made now, so that its
    ``process.start`` slice ends where the program takes over from the
    interpreter and the imports. False where this JAX has no
    ``jax.monitoring``."""
    global _dispatcher_installed, _backend_tap
    with _dispatcher_lock:
        if _dispatcher_installed:
            return True
        process_tracer()
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(
                _on_duration_event
            )
            monitoring.register_event_listener(_on_event)
        except Exception:
            return False
        if _backend_tap is None:
            _backend_tap = _BackendOpenTap()
        _dispatcher_installed = True
        return True


def _signature(args: tuple, kwargs: dict) -> tuple:
    """Cheap per-call signature: shapes/dtypes/weak_type of array leaves,
    repr of everything else — a superset of what distinguishes jit cache
    entries for the engine's call patterns."""
    out: List[object] = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            out.append(
                (
                    tuple(shape),
                    str(dtype),
                    bool(getattr(leaf, "weak_type", False)),
                )
            )
        else:
            out.append(repr(leaf))
    return tuple(out)


def _shape_str(sig: tuple) -> str:
    parts = []
    for entry in sig:
        if isinstance(entry, tuple) and len(entry) == 3:
            shape, dtype, _ = entry
            parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
    return " ".join(parts) if parts else "<no array args>"


class ProgramRecord:
    """Analysis results for one (program, signature) pair."""

    __slots__ = (
        "name",
        "signature",
        "compile_seconds",
        "flops",
        "argument_bytes",
        "output_bytes",
        "temp_bytes",
        "generated_code_bytes",
        "calls",
    )

    def __init__(self, name: str, signature: tuple):
        self.name = name
        self.signature = signature
        self.compile_seconds = 0.0
        self.flops = 0.0
        self.argument_bytes = 0
        self.output_bytes = 0
        self.temp_bytes = 0
        self.generated_code_bytes = 0
        self.calls = 0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "shapes": _shape_str(self.signature),
            "compile_seconds": self.compile_seconds,
            "flops": self.flops,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "calls": self.calls,
        }


class _LedgeredProgram:
    """Callable wrapper installed by :meth:`ProgramLedger.wrap`. The hit
    path is one dict probe on the signature; the miss path runs the AOT
    analysis and notifies the sentinel, all inside an attribution scope so
    the monitoring dispatcher knows these compile events are accounted."""

    __slots__ = ("ledger", "name", "fn", "_records")

    def __init__(self, ledger: "ProgramLedger", name: str, fn: Callable):
        self.ledger = ledger
        self.name = name
        self.fn = fn
        self._records: Dict[tuple, ProgramRecord] = {}

    def __call__(self, *args, **kwargs):
        sig = _signature(args, kwargs)
        record = self._records.get(sig)
        if record is not None:
            record.calls += 1
            return self.fn(*args, **kwargs)
        _attribution.scope = (self.name, sig)
        try:
            record = self.ledger._analyze(self.name, sig, self.fn, args, kwargs)
            self._records[sig] = record
            record.calls += 1
            # First jit execution compiles its own cache entry; keep the
            # attribution scope open so those events are not "foreign".
            return self.fn(*args, **kwargs)
        finally:
            _attribution.scope = None


class ProgramLedger:
    """Per-engine device-truth ledger (see module doc).

    ``analyze=False`` keeps the signature tracking (the sentinel's miss
    detector) but skips the extra AOT compile — for callers who want the
    sentinel without paying double compile time.
    """

    def __init__(self, analyze: bool = True):
        self.analyze = analyze
        self.programs: Dict[Tuple[str, tuple], ProgramRecord] = {}
        self.sentinel: Optional["RecompileSentinel"] = None
        self.analysis_failures = 0
        # Host<->device transfer ledger; the engine feeds byte counts at
        # its staging/readback sites and pulls per-step deltas for the
        # tracer counter tracks.
        self.bytes_h2d_total = 0
        self.bytes_d2h_total = 0
        # Optional per-source attribution: callers passing ``tag=`` to
        # count_h2d/count_d2h (e.g. the host KV tier's "hostkv_spill" /
        # "hostkv_fetch") get their bytes double-entry booked here, so a
        # subsystem's own byte counter can be cross-checked against the
        # device-truth ledger exactly.
        self.bytes_h2d_by_tag: Dict[str, int] = {}
        self.bytes_d2h_by_tag: Dict[str, int] = {}
        self._step_mark_h2d = 0
        self._step_mark_d2h = 0
        # Live-buffer HBM watermark.
        self.live_bytes = 0
        self.live_peak_bytes = 0

    # ------------------------------------------------------------- wrapping

    def wrap(self, name: str, fn: Callable) -> Callable:
        """Wrap one compiled program. Idempotent on already-wrapped fns."""
        if isinstance(fn, _LedgeredProgram):
            return fn
        return _LedgeredProgram(self, name, fn)

    def _analyze(
        self, name: str, sig: tuple, fn: Callable, args: tuple, kwargs: dict
    ) -> ProgramRecord:
        record = ProgramRecord(name, sig)
        self.programs[(name, sig)] = record
        if self.analyze:
            t0 = time.perf_counter()
            try:
                compiled = fn.lower(*args, **kwargs).compile()
            except Exception:
                self.analysis_failures += 1
                compiled = None
            record.compile_seconds = time.perf_counter() - t0
            if compiled is not None:
                self._fill_from_compiled(record, compiled)
        if self.sentinel is not None:
            self.sentinel._on_ledger_miss(name, sig)
        return record

    @staticmethod
    def _fill_from_compiled(record: ProgramRecord, compiled) -> None:
        try:
            mem = compiled.memory_analysis()
        except Exception:
            mem = None
        if mem is not None:
            record.argument_bytes = int(
                getattr(mem, "argument_size_in_bytes", 0) or 0
            )
            record.output_bytes = int(
                getattr(mem, "output_size_in_bytes", 0) or 0
            )
            record.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
            record.generated_code_bytes = int(
                getattr(mem, "generated_code_size_in_bytes", 0) or 0
            )
        try:
            cost = compiled.cost_analysis()
        except Exception:
            cost = None
        if isinstance(cost, dict):
            record.flops = float(cost.get("flops", 0.0) or 0.0)

    # ------------------------------------------------------ transfer ledger

    def count_h2d(self, nbytes: int, tag: Optional[str] = None) -> None:
        self.bytes_h2d_total += int(nbytes)
        if tag is not None:
            self.bytes_h2d_by_tag[tag] = (
                self.bytes_h2d_by_tag.get(tag, 0) + int(nbytes)
            )

    def count_d2h(self, nbytes: int, tag: Optional[str] = None) -> None:
        self.bytes_d2h_total += int(nbytes)
        if tag is not None:
            self.bytes_d2h_by_tag[tag] = (
                self.bytes_d2h_by_tag.get(tag, 0) + int(nbytes)
            )

    def step_transfer_deltas(self) -> Tuple[int, int]:
        """Bytes moved since the previous call — the per-step numbers the
        engine exports as tracer counter tracks."""
        dh2d = self.bytes_h2d_total - self._step_mark_h2d
        dd2h = self.bytes_d2h_total - self._step_mark_d2h
        self._step_mark_h2d = self.bytes_h2d_total
        self._step_mark_d2h = self.bytes_d2h_total
        return dh2d, dd2h

    # ---------------------------------------------------------- live buffers

    def update_live_bytes(self) -> int:
        """Sum the bytes of every live device array and advance the peak
        watermark. O(live arrays); the engine calls it once per step."""
        total = 0
        try:
            for arr in jax.live_arrays():
                total += int(getattr(arr, "nbytes", 0) or 0)
        except Exception:
            return self.live_bytes
        self.live_bytes = total
        if total > self.live_peak_bytes:
            self.live_peak_bytes = total
        return total

    # --------------------------------------------------------------- export

    @property
    def program_count(self) -> int:
        return len(self.programs)

    def total_compile_seconds(self) -> float:
        return sum(r.compile_seconds for r in self.programs.values())

    def total_flops(self) -> float:
        return sum(r.flops for r in self.programs.values())

    def total_temp_bytes(self) -> int:
        return sum(r.temp_bytes for r in self.programs.values())

    def total_generated_code_bytes(self) -> int:
        return sum(r.generated_code_bytes for r in self.programs.values())

    def metadata(self) -> Dict[str, Any]:
        """The tracer/statusz metadata block: every analyzed program with
        its compile time, HBM breakdown, and FLOPs."""
        return {
            "programs": [
                r.to_dict()
                for r in sorted(
                    self.programs.values(), key=lambda r: r.name
                )
            ],
            "analysis_failures": self.analysis_failures,
            "bytes_h2d_total": self.bytes_h2d_total,
            "bytes_d2h_total": self.bytes_d2h_total,
            "bytes_h2d_by_tag": dict(self.bytes_h2d_by_tag),
            "bytes_d2h_by_tag": dict(self.bytes_d2h_by_tag),
            "live_buffer_bytes": self.live_bytes,
            "live_buffer_peak_bytes": self.live_peak_bytes,
        }

    def register_into(self, registry) -> None:
        """Export the ledger through a :class:`MetricsRegistry`."""
        registry.gauge_fn(
            "xla_programs",
            lambda: float(self.program_count),
            help="Distinct (program, signature) pairs compiled",
        )
        registry.counter_fn(
            "xla_compile_seconds_total",
            self.total_compile_seconds,
            help="Wall-clock spent in ledgered XLA compilation",
        )
        registry.gauge_fn(
            "xla_program_flops",
            self.total_flops,
            help="Sum of cost-analysis FLOPs across compiled programs",
        )
        registry.gauge_fn(
            "xla_temp_bytes",
            lambda: float(self.total_temp_bytes()),
            help="Sum of memory-analysis temp HBM bytes across programs",
        )
        registry.gauge_fn(
            "xla_generated_code_bytes",
            lambda: float(self.total_generated_code_bytes()),
            help="Sum of generated-code bytes across compiled programs",
        )
        registry.gauge_fn(
            "xla_live_buffer_bytes",
            lambda: float(self.live_bytes),
            help="Bytes held by live device arrays at last step",
        )
        registry.gauge_fn(
            "xla_live_buffer_peak_bytes",
            lambda: float(self.live_peak_bytes),
            help="High-water mark of live device array bytes",
        )
        registry.counter_fn(
            "transfer_h2d_bytes_total",
            lambda: float(self.bytes_h2d_total),
            help="Host-to-device staging bytes",
        )
        registry.counter_fn(
            "transfer_d2h_bytes_total",
            lambda: float(self.bytes_d2h_total),
            help="Device-to-host readback bytes",
        )


class RecompileSentinel:
    """Post-warmup compile detector (see module doc). Construct with the
    observability sinks to fan alerts into; ``arm()`` once the engine has
    seen its full working set of shapes."""

    def __init__(
        self,
        ledger: Optional[ProgramLedger] = None,
        tracer=None,
        flight=None,
        name: str = "recompile",
    ):
        self.name = name
        self.tracer = tracer
        self.flight = flight
        self.armed = False
        self.firing = False
        self.count = 0
        self.trips: List[Dict[str, Any]] = []
        self.monitoring_available = False
        if ledger is not None:
            ledger.sentinel = self

    def arm(self) -> None:
        """Start treating every new compilation as an incident."""
        self.armed = True
        self.monitoring_available = install_dispatcher()
        _armed_sentinels.add(self)

    def disarm(self) -> None:
        self.armed = False
        _armed_sentinels.discard(self)

    # ----------------------------------------------------------- detectors

    def _on_ledger_miss(self, name: str, sig: tuple) -> None:
        if self.armed:
            self._trip(program=name, shapes=_shape_str(sig), source="ledger")

    def _on_backend_compile(self, parts: Dict[str, Any]) -> None:
        """``parts``: the ``args`` of the program's ``compile`` slice."""
        if not self.armed:
            return
        if _current_attribution() is not None:
            # A ledgered program is compiling on this thread; the ledger
            # miss already tripped (or will) with the program's name.
            return
        self._trip(
            program=parts["fun_name"],
            source="monitoring",
            compile_seconds=parts["backend_s"],
            trace_s=parts["trace_s"],
            lower_s=parts["lower_s"],
            cache=parts["cache"],
        )

    # -------------------------------------------------------------- fan-out

    def _trip(self, **fields) -> None:
        self.count += 1
        self.firing = True
        event = dict(fields)
        event["t"] = time.time()
        self.trips.append(event)
        if self.flight is not None:
            try:
                self.flight.record("recompile", **fields)
            except Exception:
                pass
        if self.tracer is not None and getattr(self.tracer, "enabled", False):
            try:
                self.tracer.instant("recompile_sentinel", **fields)
            except Exception:
                pass

    def acknowledge(self) -> None:
        """Clear the firing latch (the counter stays — it is monotonic)."""
        self.firing = False

    def status(self) -> Dict[str, Any]:
        return {
            "armed": self.armed,
            "firing": self.firing,
            "count": self.count,
            "monitoring_available": self.monitoring_available,
            "trips": list(self.trips[-16:]),
        }

    def register_into(self, registry) -> None:
        registry.counter_fn(
            "engine_recompiles_total",
            lambda: float(self.count),
            help="Post-warmup XLA compilations detected by the sentinel",
        )
        registry.gauge_fn(
            "recompile_sentinel_armed",
            lambda: float(self.armed),
            help="1 while the recompile sentinel is armed",
        )
        registry.gauge_fn(
            "recompile_sentinel_firing",
            lambda: float(self.firing),
            help="1 after a post-warmup recompile until acknowledged",
        )


__all__ = [
    "install_dispatcher",
    "ProgramLedger",
    "ProgramRecord",
    "RecompileSentinel",
]
