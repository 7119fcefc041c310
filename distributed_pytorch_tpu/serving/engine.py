"""The continuous-batching inference engine: host orchestration around a
fixed-shape jit decode step.

``submit(prompt, params) -> request_id`` / ``step()`` / ``poll(request_id)``.
Every ``step()``:

1. asks the :class:`~.scheduler.Scheduler` for a plan (admission with
   prefix-cache lookup, copy-on-write page copies, chunked prefill under
   the token budget, the batched decode set, preemption);
2. executes the CoW copies — one compiled page-copy program per shared page
   a writer is about to extend;
3. executes the prefill pieces — each ONE ``[1, width]`` jit call writing
   K/V into the request's pages (logits dead-code-eliminated), starting at
   the first token the prefix cache did not already cover. A piece is up to
   ``max_prefill_chunk`` tokens of one prompt; it runs padded to the next
   multiple of ``g = min(PREFILL_GRANULE, max_prefill_chunk)`` with its
   valid length as an operand, which the model honours (a padded token
   writes the null page, moves no recurrent state, reaches no expert and is
   counted nowhere). So the programs are the widths ``g, 2g, ...,
   max_prefill_chunk``, and the engine builds and runs them ALL when it
   needs the first: no later prompt length compiles anything;
4. dispatches ONE batched decode step over all ``max_slots`` slots —
   inactive slots are padded (null block table, length 0) and masked, so
   the decode program compiles exactly once regardless of which requests
   are live;
5. resolves the PREVIOUS step's decode readback (overlapped stepping: the
   blocking ``np.asarray`` lands while the device chews on the decode just
   dispatched), retires finished requests, records TTFT/TPOT/e2e.

Overlap mechanics: the sampled-token vector from step N is fed back into
step N+1 as a device-resident ``prev`` argument — each slot's input token
is ``where(use_prev, prev[slot], host_token)`` — so a decoding sequence's
next input never round-trips through the host. Host bookkeeping tracks the
dispatch with a PENDING placeholder that :meth:`Scheduler.resolve_decoded`
fills in one step later. ``overlap=False`` resolves synchronously (same
compiled program; ``use_prev`` is simply always 0), which is also the
behavior under a scheduler that never redispatches an unresolved slot.

The decode math is :func:`~distributed_pytorch_tpu.generation
.decode_token_step` — the SAME single-token step ``generate()``'s offline
loop runs — against the paged cache, so continuous batching is
token-for-token identical to offline decode (pinned by
``tests/test_serving.py`` on CPU), with or without prefix caching and
overlap.

Sampling determinism: each request gets ``PRNGKey(seed)`` and token i is
drawn with ``fold_in(key, i)`` — independent of batch composition, slot
assignment, and preemption, so a preempted-then-resumed request reproduces
its exact stream. Under overlap the fold index is the DISPATCH count
(``n_issued``), which equals the generated count at the same point of the
synchronous schedule. The fold happens INSIDE the compiled programs: the
host keeps each request's base key as ``uint32[2]`` host data
(:func:`~distributed_pytorch_tpu.generation.host_prng_key`, made at submit
without touching a device), stages it with the count beside it as one
``[max_slots, 3]`` operand, and ``_decode_step`` / ``_spec_step`` run
:func:`~distributed_pytorch_tpu.generation.fold_row_keys` over it. A key
fetched from the device instead would queue behind the decode program in
flight and make the host wait out the step it is meant to overlap.

Speculative serving (``draft_model``): each scheduled decode becomes one
draft+verify ROUND — gamma single-token draft steps propose a chunk, one
gamma-wide chunked target forward verifies it, and every row emits its
accepted prefix plus a correction (1..gamma tokens, per-row, no
minimum-across-batch stall). The draft model keeps its own paged pool with
the SAME (num_pages, page_size) geometry, governed by the same allocator
and block tables, so one physical page id names the same token span in
both pools and every allocation / refcount / CoW / eviction decision is
made once; prefill chunks and CoW copies simply run against both pools.
Rejected-token rollback is O(1) in both pools: ``len_cached`` stops at the
emitted count and K/V written past it is dead by construction (attention
masks positions >= seq_len, and the real continuation overwrites them
write-then-attend next round). Rounds resolve synchronously — the host
needs each row's accepted count to plan the next round — so ``overlap``
composes differently here: the round is dispatched BEFORE the step's
prefill chunks and its readback lands while they compute. Greedy rows emit
exactly the target's argmax at every position (the chunked verify logits
match the single-token path bitwise at f32), so a speculative engine is
token-identical to the plain engine; sampled rows follow Leviathan et
al.'s residual-resampling rule, keeping every emitted token exactly
target-distributed.

Recurrent layers (a model whose ``layer_types`` name ``"mamba"``,
``"mamba2"`` or ``"gated_delta"`` layers, ``models/mamba.py``,
``models/mamba2.py`` and ``models/gated_delta.py``): a request
then owns, beside its pages, ONE fixed-size state, the row of its slot in
every such layer's two ``cache`` variables (``STATE_KEYS``: for ``"mamba"``
``conv_state [max_slots, K-1, d_inner]`` and ``scan_state [max_slots, N,
d_inner]`` float32; for ``"mamba2"`` ``conv_state [max_slots, K-1, d_inner +
2 G N]`` and ``scan_state [max_slots, H, P, N]`` float32, 4 MB a slot and
layer at 128 x 64 x 128; for ``"gated_delta"`` ``conv_state [max_slots, K-1,
2 H d_k + H d_v]`` and the delta rule's MATRIX state ``scan_state
[max_slots, H / p, d_k, p d_v]`` float32, ``p`` heads side by side on the
lanes, 2.2 MB a slot and layer at 30 x 96 x 192). The engine reads that the
model has them from the model's ``recurrent_layers`` and from the cache tree
it builds, whatever the states' shapes; there is no argument for it, and
nothing below changed when the second kind of state arrived, nor the third:
the mask, the reset rule, the prefill operand, the byte count and the
refusals are keyed on ``STATE_KEYS`` and on the leaves' leading ``max_slots``
alone. The one design:

* **decode** runs all ``max_slots`` rows, row ``r`` on slot ``r``'s state in
  place. A row outside the dispatched group has a zeroed block table; the
  program derives ``state_slots = where(table[:, 0] != NULL_PAGE, r, -1)``
  from the operand it already stages, and the mixer writes a state only
  under ``state_slots >= 0``: such a row keeps both states bit for bit (a
  recurrence has no null page to absorb a masked write). A row in the group
  advances by one token. Overlap stays on: the one wasted step of a row
  that had already stopped (its ``PENDING_TOKEN`` input) runs with the
  row's old table and so writes the state of a slot that has been retired;
  nobody reads it, because
* **a position 0 starts from zeros**: whichever program carries a row whose
  ``seq_lens`` is 0 (the first prefill chunk of an admission or of a
  re-prefill after preemption, or the decode step of a one-token prompt)
  reads zeros in place of what the slot held. Nothing is zeroed ahead of
  time and no program exists for it. Each such start is a ``state.reset``
  instant in the trace (slot, request, cause ``admit`` | ``preempt``).
* **prefill** is the same ``[1, width]`` program with one more operand, the
  slot whose state the piece carries on. The recurrence is sequential in
  the token, so pieces of any sizes give the state one pass over the whole
  prompt gives, to float32 rounding of ``h`` at the piece borders (none: the
  carry is the float32 state itself); a piece's padding leaves the state
  where its last real token put it (``models/mamba.py``, "A padded piece").
* **what cannot be served yet is refused in the constructor**: the prefix
  cache (a hit skips positions whose state nobody kept: it needs a state
  snapshot at the page boundary), a draft model (a rejected proposal
  cannot be taken back out of a recurrence), the host page tier and a mesh
  (both know pages only), and ``serving/elastic.py``'s restore. ``kv_quant``
  is served: it concerns the attention layers' pages alone.

Routed expert layers (a model whose ``ffn_types`` name ``"routed"`` layers,
``models/moe.py``'s ``RoutedExperts``): the engine reads ``routed_layers``
from the model, as it reads ``recurrent_layers``. Such a model's decode and
prefill programs return one more result, ``[routed layers, n_experts] int32``:
how many of the program's tokens (of the rows that carry a request) the
router sent to each expert. ``routing_counts`` holds the last step's, as the
device arrays the programs returned, in dispatch order, for whoever reads
them (the benchmark's state probe does); the engine itself does not look at
them unless a tracer is on. Then the arrays of a step are kept until the
NEXT step's trace closes (by then the step's tokens have been read back, so
reading them waits for nothing and the overlap a traced run measures is the
untraced one) and are written as one ``moe.routing`` instant that names its
step: ``moe_programs``,
``moe_pairs_held`` and ``moe_pairs_absent`` (routed (token, expert) pairs on
experts this model holds and on the others), ``moe_rows_computed`` (rows the
grouped-product kernel multiplied for them: whole row tiles a reached expert,
a program and layer, by ``ops/grouped_matmul.py``'s ``rows_computed``, the
kernel's own tiling rule applied to these counts, so padding's share of the
MXU work is ``moe_rows_computed / moe_pairs_held``; 0 where the programs were
built with ``jax.lax.ragged_dot``), ``moe_experts_hit`` (held
experts with at least one token, summed over programs and layers) and
``moe_tokens_per_expert_max`` / ``_mean`` (over the held experts of a layer,
summed likewise). ``finish_inflight`` writes the last step's.
``stats()`` carries ``moe_product``, how the programs compute the grouped
products (``models/moe.py`` ``product_mode`` of the engine's ``paged_kernel``
and the model's widths: ``"pallas"``, ``"interpret"`` or ``"xla"``), and the
instants' summed ``moe_pairs_held`` and ``moe_rows_computed``. A mesh is
refused for such a model (no expert axis on the serving mesh yet). The decode
program tells such a model, recurrent layers or none, which rows are in the
dispatched group (``state_slots``): a row outside it reaches no expert and is
in no count.

Latent layers (a model whose ``layer_types`` name layers of
``models/transformer.py``'s ``LATENT_TYPES``, ``models/mla.py``'s
``LatentAttention``): a second KIND of page. Such a layer
keeps a pool ``cached_latent [num_pages, page_size, W]`` (a token's ``[c |
k_pe]`` in whole lanes, no head axis), where an attention layer keeps a K and
a V pool of ``[num_pages, page_size, Hkv, D]``. A layer may own MORE THAN ONE
pool, and a model's layers pools of different widths: a ``"latent_sparse"``
layer keeps ``cached_index [num_pages, page_size, index width]`` (its
indexer's key a token) beside its latent pool, a ``"latent_window"`` layer a
latent pool of its own rank. All of them live under the ONE allocator, block
table a sequence, trie and copy-on-write, because none of those asks what a
page holds or how many arrays it spans (the pages behind a window stay
allocated and are never read: one table a sequence serves every layer). The
engine reads ``latent_layers`` from the model and the pools' geometry from the
cache tree it builds (a page pool is a leaf whose first two axes are
``(num_pages, page_size)``; ``stats()["page_bytes_per_token_layer"]`` is what
one layer's pools hold a token, whatever their kind), so ``prefix_cache=True``
serves such a model as it serves any. What is built on a K and a V pool of one
head size is refused in the constructor, each with its reason: ``mesh``
(``KV_POOL_SPEC`` splits a KV-head axis), ``host_pages``
(``serving/hostkv.py`` reckons a page's bytes from ``Hkv x D``), ``kv_quant``
(one scale a (token, head)), ``draft_model`` (no verify path over latent
pages). A model with sparse layers' decode program also returns the positions
each sparse layer selected, ``selected_positions`` (the last step's, ``[sparse
layers, slots, index_top_k]``, -1 past a row's own); nobody reads them back
but a caller that asks.

Window layers (a model whose ``layer_types`` name ``"attention_window"``
layers: K/V attention with a window of its own, ``model.kv_window``): a second
block-table GROUP. The full layers keep the sequence's one table, the
allocator, the pool size ``num_pages`` and every program operand they have in
a model without such layers; the window layers' pools hold ``window_pages``
pages under an allocator of their own, and a sequence holds a second table
there (``serving/kv_cache.py`` ``WindowTable`` / ``WindowGroup``), whose pages
wholly behind its window go back after every prefill piece and decode
dispatch: ``window_pages(W, page)`` a decoding row and ``window_group_pages``
inside a piece, where one table a sequence would hold the whole context in
every layer. The programs take one more operand, the group's SHORT tables
(``[max_slots, decode pages]`` for the decode program, ``[1, piece pages]`` for
a prefill piece: entry 0 the page that holds the window's first key), staged
from the sequence's table by the rule the layer counts its positions by
(``window_first_page``). The scheduler ensures, releases and preempts in both
groups; ``close()`` holds both allocators quiescent. ``stats()`` and the
``step`` slice carry ``window_pages_freed`` / ``_held`` / ``_free`` (``stats()``
also ``window_pages_held_peak``, the most ONE sequence held at once), a
``window.free`` instant a step that frees (``pages``, ``rows``), the
``prefill.chunk`` slice ``window_pages``, ``engine.init.pools`` each group's
bytes, the scheduler's ``admit`` event the pages a group the request comes to
hold. Refused for such a model, each with its reason: ``prefix_cache=True`` (a
hit would need the window layers' last ``W - 1`` positions, whose pages are
gone), ``draft_model``, ``host_pages``, ``mesh``, ``kv_quant``. A model without
such layers builds ONE group, and its programs lower to the bytes they always
did.

What a decode dispatch READS (which kernels the model's layers call, at which
block, under which copy rule), what the ``step`` slice and ``stats()`` count of
it (``decode_kv_tokens_*``, ``decode_rows_grouped``, ``decode_page_copies``,
``decode_pages_in_runs``, ``decode_index_tokens_*``, ``decode_window_tokens_*``,
``state_slots_updated``, ``state_bytes_moved``) and what the decode program
tells its kernels of its rows (which share a document's pages, which of their
pages stand side by side) is ``serving/decode_reads.py``'s: the engine builds
one :class:`~.decode_reads.DecodeReads` from its decode model and cache tree,
hands it each dispatch's staged tables and positions ONCE, and knows no kind
of layer for the sake of a counter.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from distributed_pytorch_tpu import chaos
from distributed_pytorch_tpu.generation import (
    decode_chunk_step,
    decode_token_step,
    fold_row_keys,
    host_prng_key,
    make_row_sampler,
    truncate_logits,
)
from distributed_pytorch_tpu.models.mamba import STATE_KEYS
from distributed_pytorch_tpu.models.moe import product_mode
from distributed_pytorch_tpu.obs import MetricsRegistry, Tracer
from distributed_pytorch_tpu.obs.flight import (
    NULL_FLIGHT_RECORDER,
    FlightRecorder,
)
from distributed_pytorch_tpu.obs.goodput import (
    GoodputTracker,
    count_params,
    peak_flops_per_chip,
    transformer_decode_flops_per_token,
)
from distributed_pytorch_tpu.obs.regress import RegressionDetector
from distributed_pytorch_tpu.obs.roofline import RooflineModel
from distributed_pytorch_tpu.obs.slo import SLOMonitor, SLObjective
from distributed_pytorch_tpu.obs.timeseries import TimeSeriesDB
from distributed_pytorch_tpu.obs.tracer import (
    NULL_TRACER,
    _PID_REQUESTS,
    process_tracer,
)
from distributed_pytorch_tpu.obs.xla import ProgramLedger, RecompileSentinel
from distributed_pytorch_tpu.ops.grouped_matmul import rows_computed
from distributed_pytorch_tpu.serving.admission import (
    AdmissionController,
    ServingMetrics,
)
from distributed_pytorch_tpu.serving.decode_reads import DecodeReads
from distributed_pytorch_tpu.serving.hostkv import HostPageTier
from distributed_pytorch_tpu.serving.kv_cache import (
    NULL_PAGE,
    PagedBlockAllocator,
    PagePoolGroup,
    PrefixCache,
    WindowGroup,
    window_span_pages,
)
from distributed_pytorch_tpu.serving.mods import AdapterStore, Mods, ModState
from distributed_pytorch_tpu.serving.mesh import (
    axis_sizes,
    kv_pool_shardings,
    mesh_fingerprint,
    replicated,
    serving_param_shardings,
    validate_kv_heads,
)
from distributed_pytorch_tpu.serving.scheduler import (
    PENDING_TOKEN,
    Request,
    SamplingParams,
    Scheduler,
)


def _staged(buf: np.ndarray) -> jax.Array:
    """A reused host staging buffer as a device operand, copied on the host
    first. A backend may read the array it is handed only once the device
    gets to the transfer (the CPU backend aliases an aligned NumPy buffer
    and queues the read behind the program in flight), and nothing in a
    dispatch waits for the device, so the next refill of the buffer can
    come sooner than that. The copy is the transfer's alone."""
    return jnp.array(buf.copy())


class _PhaseSpan:
    """Accounted step-phase context: enters the tracer's phase slice,
    applies any chaos ``slow_program`` stall inside it, and accumulates
    the phase's wall time into the engine's per-step ``_acct["phases"]``
    scratch — the per-phase series the TSDB records and the regression
    detector attributes blame with. Built by ``InferenceEngine._phase``
    only when accounting or a perf fault is active."""

    __slots__ = ("engine", "name", "stall", "args", "_ctx", "_t0")

    def __init__(self, engine, name: str, stall: float, args: dict):
        self.engine = engine
        self.name = name
        self.stall = stall
        self.args = args

    def note(self, **args) -> None:
        self._ctx.note(**args)

    def __enter__(self):
        self._ctx = self.engine.tracer.phase(self.name, **self.args)
        self._ctx.__enter__()
        self._t0 = time.perf_counter()
        if self.stall > 0.0:
            time.sleep(self.stall)
        return self

    def __exit__(self, *exc):
        acct = self.engine._acct
        if acct is not None:
            phases = acct["phases"]
            phases[self.name] = (
                phases.get(self.name, 0.0)
                + (time.perf_counter() - self._t0)
            )
        return self._ctx.__exit__(*exc)


@dataclasses.dataclass(frozen=True)
class RequestStatus:
    """Snapshot returned by :meth:`InferenceEngine.poll`."""

    req_id: int
    state: str
    prompt_len: int
    generated: List[int]
    finished: bool
    preempt_count: int


class InferenceEngine:
    """Continuous-batching engine over a paged KV cache.

    ``model`` is the TRAINING-mode module (same contract as ``generate``);
    it is cloned with ``decode=True, page_size, num_pages`` internally.
    ``num_pages`` defaults to exactly enough pages for every slot to hold
    ``max_seq_len`` tokens (+1 for the reserved null page) — i.e. no
    overcommit; pass a smaller value to exercise preemption and cache
    eviction.

    ``prefix_cache=True`` shares page-aligned K/V across requests with a
    common prompt prefix (retired pages idle on an LRU instead of freeing);
    ``overlap=True`` defers each decode readback by one step so host
    scheduling hides under device compute. Both default on — outputs are
    bitwise-identical either way. ``debug=True`` re-enables the
    O(num_pages) allocator invariant sweep after every schedule.

    ``top_k``/``top_p`` are engine-static (compiled into the decode step);
    temperature and seed are per-request (:class:`SamplingParams`).

    ``draft_model``/``draft_params`` switch every decode to speculative
    draft+verify rounds of ``gamma`` proposals (see module doc); the draft
    must share the target's vocabulary and gets its own paged pool with
    identical page geometry, moved in lockstep by the shared allocator.
    Greedy requests stay token-identical to the plain engine; sampled
    requests stay exactly target-distributed (but draw a different stream
    than the plain engine — one uniform per proposal, not per token).

    ``mesh`` (a ``("data", "model")`` mesh from
    :func:`~distributed_pytorch_tpu.serving.mesh.make_serving_mesh`)
    shards the whole device side: weights follow the Megatron rules
    rebound onto ``model``, every per-layer KV page pool splits its
    KV-head dim over ``model``, and all five compiled programs become
    pjit-style sharded programs with explicit in/out shardings — the SPMD
    partitioner inserts the collectives while the host-side allocator,
    block tables, scheduler, and prefix trie stay byte-for-byte unchanged
    (pages are metadata to them). ``mesh=None`` (default) keeps today's
    single-device jit path untouched; a ``(1, 1)`` mesh is
    bitwise-identical to it, larger meshes are greedy-token-identical
    (sharded reductions reorder float accumulation).
    """

    def __init__(
        self,
        model,
        params,
        *,
        max_slots: int = 8,
        max_seq_len: int = 256,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        token_budget: int = 64,
        max_prefill_chunk: int = 32,
        max_queue: int = 128,
        max_queue_tokens: Optional[int] = None,
        top_k: int = 0,
        top_p: float = 0.0,
        prefix_cache: bool = True,
        overlap: bool = True,
        draft_model=None,
        draft_params=None,
        gamma: int = 4,
        mesh: Optional[Mesh] = None,
        debug: bool = False,
        tracer: Optional[Tracer] = None,
        trace_path: Optional[str] = None,
        flight: Optional[FlightRecorder] = None,
        slo: Optional[Sequence[SLObjective]] = None,
        goodput=None,
        xla_ledger=None,
        timeseries=None,
        max_live_adapters: int = 4,
        host_pages: Optional[int] = None,
        paged_kernel=False,
        kv_quant: Optional[str] = None,
        window_pages: Optional[int] = None,
    ):
        # Set-up slices go to the process's tracer, whatever ``tracer`` is
        # (made first, so that its ``process.start`` ends before them):
        # ``engine.init`` is written at the end of this constructor.
        setup = process_tracer()
        t_init = time.perf_counter()
        if max_seq_len % page_size:
            raise ValueError(
                f"max_seq_len {max_seq_len} must be a multiple of "
                f"page_size {page_size}"
            )
        self.pages_per_seq = max_seq_len // page_size
        if num_pages is None:
            num_pages = max_slots * self.pages_per_seq + 1
        self.page_size = page_size
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.params = params
        self.overlap = overlap
        self._top_k = int(top_k)
        self._top_p = float(top_p)
        self.speculative = draft_model is not None
        if self.speculative:
            if draft_params is None:
                raise ValueError("draft_model requires draft_params")
            if gamma < 1:
                raise ValueError(f"gamma must be >= 1, got {gamma}")
            if getattr(draft_model, "vocab_size", None) != getattr(
                model, "vocab_size", None
            ):
                raise ValueError(
                    f"draft vocab {getattr(draft_model, 'vocab_size', None)}"
                    f" != target vocab {getattr(model, 'vocab_size', None)}"
                    " — draft proposals index the target's distribution"
                )
        self.gamma = int(gamma) if self.speculative else 0
        self.draft_params = draft_params
        # Layers that keep a per-slot recurrent state (module docstring):
        # read from the model, never asked of the caller.
        self.state_layers = int(getattr(model, "recurrent_layers", 0))
        if self.state_layers:
            for given, what, why in (
                (prefix_cache, "prefix_cache=True",
                 "a prefix hit skips positions whose recurrent state nobody "
                 "kept (it needs a state snapshot at the page boundary)"),
                (draft_model is not None, "draft_model",
                 "a rejected proposal cannot be rolled back out of a "
                 "recurrent state"),
                (host_pages, "host_pages",
                 "the host tier spills and fetches KV pages only, not "
                 "recurrent state"),
                (mesh is not None, "mesh",
                 "the serving mesh shards KV page pools only, not "
                 "recurrent state"),
            ):
                if given:
                    raise ValueError(
                        f"a model with recurrent layers cannot be served "
                        f"with {what} yet: {why}"
                    )
        # Layers that keep latent pages (module docstring): ONE pool a layer
        # with no head axis. Read from the model; what knows a K and a V pool
        # of one head size is refused with its reason.
        self.latent_layers = int(getattr(model, "latent_layers", 0))
        self.selected_positions: List[jax.Array] = []  # the last step's
        if self.latent_layers:
            for given, what, why in (
                (mesh is not None, "mesh",
                 "the serving mesh splits a page pool over its KV-head "
                 "axis, and a latent pool has none"),
                (host_pages, "host_pages",
                 "the host tier reckons a page's bytes from KV heads and a "
                 "head size"),
                (kv_quant, "kv_quant",
                 "int8 pages keep one scale a (token, head), and a latent "
                 "has no head"),
                (draft_model is not None, "draft_model",
                 "the speculative verify step has no path over latent pages"),
            ):
                if given:
                    raise ValueError(
                        f"a model with latent layers cannot be served with "
                        f"{what} yet: {why}"
                    )
        # Layers that attend inside a window on a block-table GROUP of their
        # own (``serving/kv_cache.py`` ``WindowGroup``): read from the model.
        # The full layers' table, allocator and programs are what they are
        # without; what cannot follow a second group is refused.
        self.kv_window = int(getattr(model, "kv_window", 0))
        if self.kv_window:
            for given, what, why in (
                (prefix_cache, "prefix_cache=True",
                 "a prefix hit would need the window layers' last "
                 f"{self.kv_window - 1} positions, whose pages have gone "
                 "back to the group's allocator"),
                (draft_model is not None, "draft_model",
                 "a speculative round has no path over two table groups"),
                (host_pages, "host_pages",
                 "the host tier names pages by the prefix trie's chain"),
                (mesh is not None, "mesh",
                 "the serving mesh places ONE group's pools and tables"),
                (kv_quant, "kv_quant",
                 "the windowed call reads no scale pools"),
            ):
                if given:
                    raise ValueError(
                        f"a model with a window group cannot be served with "
                        f"{what} yet: {why}"
                    )
        elif window_pages is not None:
            raise ValueError(
                "window_pages sizes a window group's pool, and this model "
                "has no attention_window layers"
            )
        # Layers that route tokens to experts (module docstring): their
        # programs return the routing counts, read only under a tracer.
        self.routed_layers = int(getattr(model, "routed_layers", 0))
        if self.routed_layers and mesh is not None:
            raise ValueError(
                "a model with routed expert layers cannot be served with a "
                "mesh yet: the serving mesh has no expert axis"
            )
        self.routing_counts: List[jax.Array] = []  # the last step's programs'
        self._routing_due: Optional[Tuple[int, List[jax.Array]]] = None
        # Totals of the ``moe.routing`` instants (a tracer's runs only).
        self.moe_pairs_held = 0
        self.moe_rows_computed = 0

        # Mesh geometry is engine-static, like top_k/top_p: it is compiled
        # into every program and fingerprinted into elastic snapshots.
        # Head-divisibility is refused HERE (readable head counts), before
        # the per-kernel divisibility pass in make_param_specs.
        self.mesh = mesh
        self.mesh_fingerprint = mesh_fingerprint(mesh)
        self._data_size, self._model_size = axis_sizes(mesh)
        self._sharded_programs = 0
        if mesh is not None:
            validate_kv_heads(model, mesh, role="target")
            if self.speculative:
                validate_kv_heads(draft_model, mesh, role="draft")

        # Fused paged-attention read path + int8 KV pages (ops/
        # paged_attention.py). ``paged_kernel`` accepts False/None (off),
        # True/"auto" (Pallas on TPU, XLA reference elsewhere), or an
        # explicit mode ("pallas" | "interpret" | "xla"). ``kv_quant``
        # accepts None/"" (fp pages) or "int8". Both are engine-static like
        # the mesh: compiled into every program and fingerprinted into
        # elastic snapshots (kv_fingerprint). The clone kwargs are added
        # ONLY when set so the kernel-off engine's decode model — and its
        # compiled programs — stay byte-identical to before.
        if kv_quant not in (None, "", "int8"):
            raise ValueError(
                f"unknown kv_quant {kv_quant!r} (expected None or 'int8')"
            )
        self.kv_quant = kv_quant or ""
        self.kv_fingerprint = "int8" if self.kv_quant else "fp"
        self.paged_kernel = (
            "" if not paged_kernel
            else ("auto" if paged_kernel is True else str(paged_kernel))
        )
        # How the programs compute the routed layers' grouped products.
        self.moe_product = (
            product_mode(self.paged_kernel, model.d_model, model.d_ff)
            if self.routed_layers else ""
        )
        clone_kw = {}
        if self.paged_kernel:
            clone_kw["paged_kernel"] = self.paged_kernel
            if mesh is not None:
                # The kernel shard_maps its head dim over the mesh's
                # "model" axis — the same split KV_POOL_SPEC already gives
                # the pools — so it runs per-shard under the pjit programs.
                clone_kw["mesh"] = mesh
        if self.kv_quant:
            clone_kw["kv_quant"] = self.kv_quant
        self.window_group = None
        if self.kv_window:
            # The group's pool: by default what every slot inside a piece
            # would hold, so that nothing preempts.
            piece = window_span_pages(
                self.kv_window, page_size, max_prefill_chunk
            )
            if window_pages is None:
                window_pages = max_slots * piece + 1
            if window_pages < piece + 1:
                raise ValueError(
                    f"window_pages {window_pages} cannot hold one piece's "
                    f"{piece} pages and the null page"
                )
            group = WindowGroup(
                PagedBlockAllocator(window_pages), window=self.kv_window,
                page_size=page_size, chunk=max_prefill_chunk,
            )
            self.window_group = group
            clone_kw["window_num_pages"] = window_pages
        self.decode_model = model.clone(
            decode=True, page_size=page_size, num_pages=num_pages, **clone_kw
        )
        # Size the paged pool from abstract shapes only (eval_shape traces
        # init without running it); token length 1 — pool shapes depend only
        # on (num_pages, page_size), never on the init input.
        def _zero_cache(decode_model):
            abstract = jax.eval_shape(
                decode_model.init,
                jax.random.PRNGKey(0),
                jnp.zeros((max_slots, 1), jnp.int32),
            )["cache"]
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), abstract
            )

        # The draft pool shares (num_pages, page_size) with the target pool
        # — same page ids, same block tables, one allocator — so every page
        # lifecycle decision moves both pools in lockstep. Head/width can
        # differ freely; only the page GEOMETRY must match.
        t_pools = time.perf_counter()
        pools = {"target": _zero_cache(self.decode_model)}
        # What the target's pages hold, read from the pools it declared
        # (leaves ``[num_pages, page_size, ...]``), whatever kind they are:
        # bytes a token in one layer.
        page_leaves = [
            (path, leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                pools["target"]
            )[0]
            if leaf.shape[:2] == (num_pages, page_size)
            and getattr(path[-1], "key", None) not in STATE_KEYS
        ]
        paged_layers = len({str(path[:-1]) for path, _ in page_leaves})
        self.page_bytes_per_token_layer = (
            sum(leaf.nbytes for _, leaf in page_leaves)
            // max(1, paged_layers * num_pages * page_size)
        )
        # What a decode dispatch reads of these pools and what its program
        # is told of its rows: which kernels the layers call, at which
        # block, under which copy rule (``serving/decode_reads.py``).
        self.reads = DecodeReads(
            self.decode_model, pools["target"], max_slots=max_slots,
            pages_per_seq=self.pages_per_seq,
        )
        # Bytes of recurrent state one slot owns, over every layer.
        self.state_bytes_per_slot = sum(
            leaf.nbytes // max_slots
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                pools["target"]
            )[0]
            if getattr(path[-1], "key", None) in STATE_KEYS
        )
        self.state_resets = 0
        # Prefill programs run, the tokens they carried and the widths they
        # were padded to (``stats()``; the step slice has each step's).
        self.prefill_programs = 0
        self.prefill_tokens = 0
        self.prefill_width = 0
        if self.speculative:
            self.draft_decode_model = draft_model.clone(
                decode=True, page_size=page_size, num_pages=num_pages,
                **clone_kw,
            )
            pools["draft"] = _zero_cache(self.draft_decode_model)
        self.pools = PagePoolGroup(**pools)

        # Place the device state ONCE at init: params under the Megatron
        # rules (rebound to "model"), every KV pool with heads split over
        # "model", and one shared replicated sharding for the host-staged
        # program inputs. The compiled programs' donated-cache out
        # shardings keep the pools in place steady-state, so no resharding
        # ever happens on the hot path.
        if mesh is not None:
            self._replicated = replicated(mesh)
            self._param_shardings = serving_param_shardings(mesh, params)
            self.params = jax.device_put(params, self._param_shardings)
            if self.speculative:
                self._draft_param_shardings = serving_param_shardings(
                    mesh, draft_params
                )
                self.draft_params = jax.device_put(
                    draft_params, self._draft_param_shardings
                )
            self._pool_shardings = {
                name: kv_pool_shardings(mesh, self.pools[name])
                for name in self.pools.names
            }
            for name in self.pools.names:
                self.pools[name] = jax.device_put(
                    self.pools[name], self._pool_shardings[name]
                )
        setup.setup_slice(
            "engine.init.pools", t_pools, time.perf_counter() - t_pools,
            bytes=sum(
                leaf.nbytes
                for name in self.pools.names
                for leaf in jax.tree_util.tree_leaves(self.pools[name])
            ),
            state_bytes=self.state_bytes_per_slot * max_slots,
            **self.pool_bytes_by_group(),
        )

        # Zero-cost-when-disabled observability handle: one shared null
        # object serves every untraced engine — no timestamps, no dicts,
        # bitwise-identical outputs (pinned by tests).
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if mesh is not None and self.tracer.enabled:
            # Unsharded traces stay byte-identical: the label is only set
            # (and only serialized) for meshed engines.
            self.tracer.set_engine_label(f"mesh {self.mesh_fingerprint}")
        self.flight = flight if flight is not None else NULL_FLIGHT_RECORDER
        self.allocator = PagedBlockAllocator(num_pages)
        self.allocator.tracer = self.tracer
        self.allocator.flight = self.flight
        self.allocator.pool_names = self.pools.names
        if self.window_group is not None:
            self.window_group.allocator.pool_names = ("target/window",)
        self.prefix_cache = (
            PrefixCache(self.allocator, page_size) if prefix_cache else None
        )
        # Host-memory page tier (serving/hostkv.py): ``host_pages`` > 0
        # preallocates that many host pages per pool and attaches the
        # tier behind the prefix trie — evicted full pages spill d2h
        # instead of being lost, and a later prefix hit on a spilled
        # chain fetches h2d during admission, overlapped with decode.
        # Token outputs are bitwise-identical tier on or off (the
        # fetched K/V is the same content a re-prefill would recompute).
        if host_pages:
            if self.prefix_cache is None:
                raise ValueError(
                    "host_pages requires prefix_cache=True — host pages "
                    "are named by the prefix trie's content-addressed "
                    "key chain"
                )
            self.hostkv = HostPageTier(
                {name: self.pools[name] for name in self.pools.names},
                num_host_pages=int(host_pages),
                page_size=page_size,
                gather_fn=self._gather_page,
            )
            self.prefix_cache.host = self.hostkv
        else:
            self.hostkv = None
        self.scheduler = Scheduler(
            self.allocator,
            max_slots=max_slots,
            page_size=page_size,
            pages_per_seq=self.pages_per_seq,
            token_budget=token_budget,
            max_prefill_chunk=max_prefill_chunk,
            prefix_cache=self.prefix_cache,
            gamma=self.gamma,
            debug=debug,
            tracer=self.tracer,
            flight=self.flight,
            **(
                {"window_group": self.window_group}
                if self.window_group is not None else {}
            ),
        )
        self.admission = AdmissionController(
            max_queue=max_queue,
            max_request_tokens=max_seq_len,
            max_queue_tokens=max_queue_tokens,
        )
        self.metrics = ServingMetrics(speculative=self.speculative)
        self.vocab_size = int(getattr(model, "vocab_size", 0))
        # Per-request LoRA adapters: merged-weight trees are full model
        # copies, so they get the KV-page treatment — an LRU device cache
        # capped at ``max_live_adapters``. Unsharded engines only (a
        # merged tree would need re-placement under the param shardings);
        # submit() refuses adapter mods on meshed/speculative engines.
        self.adapters = AdapterStore(self.params, max_live=max_live_adapters)
        # Elastic lifecycle counters (serving/elastic.py increments the
        # first three; close() flips _closed). Surfaced via the registry so
        # a drill can cross-check them against ground truth.
        self.drains = 0
        self.restores = 0
        self.requests_recovered = 0
        self.trace_path = trace_path
        self._closed = False
        # Goodput accounting: ``goodput=True`` builds a tracker configured
        # from the model's own dims (decode FLOPs-per-token at half the max
        # context, peak FLOPs from the local device kind); pass a
        # pre-configured GoodputTracker for full control. ``_acct`` is the
        # per-step scratch dict the accounting wrapper threads through
        # ``_step_impl`` — None whenever no step is being accounted.
        if goodput is True:
            self.goodput = self._default_goodput(model)
        else:
            self.goodput = goodput if goodput else None
        self._acct: Optional[dict] = None
        # Device-truth accounting (obs/xla.py). ``xla_ledger=True`` (or a
        # pre-built ProgramLedger) wraps every compiled program: first call
        # per signature runs an analysis-only AOT compile recording wall
        # time / memory_analysis HBM / cost-analysis FLOPs, and the engine
        # counts host<->device staging/readback bytes per step. Execution
        # always goes through the original jit callable, so tokens are
        # bitwise-identical ledger-on vs -off. The paired RecompileSentinel
        # (``arm_recompile_sentinel()`` after warmup) turns any later
        # compilation into a counted, flight-recorded alert. Must be chosen
        # at construction — programs are wrapped as they are built.
        if xla_ledger:
            self.xla = (
                xla_ledger
                if isinstance(xla_ledger, ProgramLedger)
                else ProgramLedger()
            )
            self.sentinel = RecompileSentinel(
                self.xla, tracer=self.tracer, flight=self.flight
            )
        else:
            self.xla = None
            self.sentinel = None
        # The performance observatory (obs/timeseries.py + obs/regress.py
        # + obs/roofline.py). ``timeseries=True`` builds a default TSDB;
        # pass a TimeSeriesDB for custom resolutions. Every registry
        # counter/gauge plus the derived per-step series is sampled each
        # accounted step; the CUSUM regression detector rides the same
        # feed, and — when the XLA ledger is also on — a RooflineModel
        # joins ledger bytes/FLOPs with the chip peaks. Pure host-side
        # bookkeeping off the device path: tokens are bitwise-identical
        # observatory-on vs -off (pinned in tests and the perfwatch bench).
        if timeseries:
            self.timeseries = (
                timeseries
                if isinstance(timeseries, TimeSeriesDB)
                else TimeSeriesDB()
            )
            self.regress = RegressionDetector(
                flight=self.flight, tracer=self.tracer
            )
        else:
            self.timeseries = None
            self.regress = None
        if self.timeseries is not None and self.xla is not None:
            self.roofline = RooflineModel(
                self.xla,
                self.timeseries,
                device=jax.devices()[0],
                fallback_flops_fn=self._analytic_program_flops(model),
            )
        else:
            self.roofline = None
        # Introspection server handle (serve()/close()); while attached,
        # step()/submit() run under the registry lock so scrapes observe
        # step boundaries only.
        self._server = None
        self.registry = self._build_registry()
        if self.timeseries is not None:
            self.timeseries.track_registry(self.registry)
        # SLO burn-rate monitoring reads the registry it writes its
        # verdicts into, so one snapshot carries metrics AND alerts.
        self.slo = (
            SLOMonitor(
                self.registry, slo, tracer=self.tracer, flight=self.flight
            )
            if slo
            else None
        )
        # Flight-recorder postmortems must be written BEFORE an injected
        # fault SIGKILLs the process: chaos notifies observers first.
        if self.flight.enabled:
            chaos.add_fault_observer(self._on_chaos_fault)
        self.requests: Dict[int, Request] = {}
        self._next_id = 0
        # req_id -> the request's base key, host data (host_prng_key).
        self._keys: Dict[int, np.ndarray] = {}

        # Reusable host staging buffers for the batched decode inputs —
        # refilled in place every step instead of reallocated. Rows for
        # inactive slots MUST be re-zeroed each step (a stale block-table
        # row would scatter the masked write into a page some other request
        # now owns). They go to the device through _staged, which copies
        # them on the host first: the next refill must not reach a dispatch
        # that has yet to read them.
        self._stage_tokens = np.zeros((max_slots,), np.int32)
        self._stage_tables = np.zeros(
            (max_slots, self.pages_per_seq), np.int32
        )
        self._stage_lens = np.zeros((max_slots,), np.int32)
        if self.window_group is not None:
            # The window group's short tables, a decode row's pages wide.
            self._stage_window_tables = np.zeros(
                (max_slots, self.window_group.decode_pages), np.int32
            )
            self._window_seen = (0, 0)  # pages freed, trims: last step's end
        self._stage_temps = np.zeros((max_slots,), np.float32)
        # A row's base key and, beside it, the index of the token it is
        # about to draw: the programs fold the one into the other.
        self._stage_keys = np.zeros((max_slots, 3), np.uint32)
        self._stage_use_prev = np.zeros((max_slots,), np.int32)
        self._zero_prev = jnp.zeros((max_slots,), jnp.int32)
        # Fixed-shape additive-logit operand for per-request mods. The
        # all-zeros device constant serves every dispatch with no modded
        # rows (no extra host->device bytes on the mods-off path); the
        # host buffer is filled per group only when some row carries a
        # bias/grammar row.
        self._stage_bias = np.zeros(
            (max_slots, self.vocab_size), np.float32
        )
        self._zero_bias = jnp.zeros(
            (max_slots, self.vocab_size), jnp.float32
        )
        if mesh is not None:
            self._zero_prev = jax.device_put(
                self._zero_prev, self._replicated
            )
            self._zero_bias = jax.device_put(
                self._zero_bias, self._replicated
            )
        # (sampled-token device array, decode slots, their requests) of the
        # not-yet-resolved dispatch, or None.
        self._inflight: Optional[
            Tuple[jax.Array, List[int], List[Request]]
        ] = None
        setup.setup_slice(
            "engine.init", t_init, time.perf_counter() - t_init,
            slots=max_slots, pages=num_pages,
        )

    def pool_bytes_by_group(self) -> dict:
        """What ``engine.init.pools`` says of a model with a window group:
        each group's pools' bytes (nothing where there is one group)."""
        if self.window_group is None:
            return {}
        kinds = self.decode_model.layer_types
        by_group = {"window_bytes": 0, "full_bytes": 0}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            self.pools["target"]
        )[0]:
            block, _, layer = str(getattr(path[0], "key", "")).partition("_")
            windowed = (
                block == "block" and layer.isdigit()
                and kinds[int(layer)] == "attention_window"
            )
            by_group["window_bytes" if windowed else "full_bytes"] += (
                leaf.nbytes
            )
        return by_group

    def _default_goodput(self, model) -> GoodputTracker:
        """A :class:`GoodputTracker` configured from the engine's own
        geometry: decode FLOPs-per-token from the analytic transformer
        model at half the max context (the mean context of a sequence
        decoded to the limit), peak FLOPs from the local device kind, and
        the mesh's device count."""
        n_params = count_params(self.params)
        embed = getattr(model, "vocab_size", 0) * getattr(
            model, "d_model", 0
        )
        n_heads = max(1, getattr(model, "n_heads", 1))
        head_dim = getattr(model, "d_model", 0) // n_heads
        fpt = transformer_decode_flops_per_token(
            n_params=n_params,
            embed_params=min(embed, n_params),
            n_layers=getattr(model, "n_layers", 0),
            n_heads=n_heads,
            head_dim=head_dim,
            context_len=self.max_seq_len // 2,
        )
        return GoodputTracker(
            flops_per_token=fpt,
            peak_flops_per_device=peak_flops_per_chip(jax.devices()[0]),
            n_devices=max(1, self._data_size * self._model_size),
        )

    def _analytic_program_flops(self, model):
        """Fallback FLOPs-per-call estimator for the roofline model, used
        when a ledgered program's ``cost_analysis`` reports 0 (the CPU
        backend omits flops — the same gap the goodput MFU path fills with
        the analytic transformer model). Maps each engine program to the
        decode FLOPs-per-token model by its token count per call."""
        n_params = count_params(self.params)
        embed = getattr(model, "vocab_size", 0) * getattr(
            model, "d_model", 0
        )
        n_heads = max(1, getattr(model, "n_heads", 1))
        head_dim = getattr(model, "d_model", 0) // n_heads
        fpt = transformer_decode_flops_per_token(
            n_params=n_params,
            embed_params=min(embed, n_params),
            n_layers=getattr(model, "n_layers", 0),
            n_heads=n_heads,
            head_dim=head_dim,
            context_len=self.max_seq_len // 2,
        )
        max_slots, gamma = self.max_slots, self.gamma

        def flops_for(record) -> float:
            name = record.name
            if "prefill_step_c" in name:
                try:
                    return fpt * int(name.rsplit("c", 1)[1])
                except ValueError:
                    return fpt
            if name.startswith("decode_step"):
                return fpt * max_slots
            if name.startswith("spec_step"):
                # gamma draft steps + one gamma-wide verify per slot.
                return fpt * max_slots * (2 * gamma)
            return 0.0  # copy_page and friends move bytes, not FLOPs

        return flops_for

    def _build_registry(self) -> MetricsRegistry:
        """Every serving metric registered into one ``serving_``-namespaced
        :class:`MetricsRegistry`: the :class:`ServingMetrics` counters and
        latency reservoirs (resolved through ``self.metrics`` at snapshot
        time, so swapping the metrics object — bench's warm-up reset —
        stays correct), admission counters, scheduler pressure, and the
        allocator's O(1) page-state gauges. Pull-based: the owning objects
        keep their plain attributes as the single source of truth."""
        reg = MetricsRegistry(namespace="serving")
        ServingMetrics.register_into(reg, lambda: self.metrics)
        self.admission.register_into(reg)
        reg.counter_fn(
            "preemptions_total", lambda: self.scheduler.preemptions
        )
        reg.counter_fn("drains_total", lambda: self.drains)
        reg.counter_fn("restores_total", lambda: self.restores)
        reg.counter_fn(
            "requests_recovered_total", lambda: self.requests_recovered
        )
        reg.counter_fn(
            "requests_expired_total", lambda: self.scheduler.expired
        )
        reg.counter_fn(
            "requests_cancelled_total", lambda: self.scheduler.cancelled
        )
        reg.counter_fn(
            "adapter_cache_hits_total", lambda: self.adapters.hits
        )
        reg.counter_fn(
            "adapter_cache_misses_total", lambda: self.adapters.misses
        )
        reg.counter_fn(
            "adapter_evictions_total", lambda: self.adapters.evictions
        )
        reg.gauge_fn(
            "adapters_live", lambda: len(self.adapters.live)
        )
        reg.counter_fn(
            "cow_copies_total", lambda: self.allocator.cow_copies
        )
        reg.counter_fn(
            "page_evictions_total", lambda: self.allocator.evictions
        )
        reg.gauge_fn(
            "pages_free", lambda: self.allocator.counters()["pages_free"]
        )
        reg.gauge_fn(
            "pages_referenced", lambda: self.allocator.num_allocated
        )
        reg.gauge_fn("pages_cached_idle", lambda: self.allocator.num_idle)
        if self.state_layers:
            reg.gauge_fn(
                "state_slots_in_use", lambda: len(self.scheduler.running)
            )
            reg.gauge_fn("state_bytes", self._state_bytes)
            reg.counter_fn("state_resets_total", lambda: self.state_resets)
        reg.gauge_fn("queue_depth", lambda: self.scheduler.num_waiting)
        reg.gauge_fn(
            "running_requests", lambda: len(self.scheduler.running)
        )
        if self.prefix_cache is not None:
            pc = self.prefix_cache
            reg.counter_fn("prefix_lookups_total", lambda: pc.lookups)
            reg.counter_fn("prefix_hits_total", lambda: pc.hits)
            reg.counter_fn("prefix_tokens_hit_total", lambda: pc.tokens_hit)
            reg.counter_fn(
                "prefix_tokens_missed_total", lambda: pc.tokens_missed
            )
            reg.counter_fn(
                "prefix_tokens_hit_host_total", lambda: pc.tokens_hit_host
            )
            reg.gauge_fn("prefix_nodes", lambda: pc.num_nodes)
        if self.hostkv is not None:
            hk = self.hostkv
            reg.counter_fn("hostkv_spills_total", lambda: hk.spills)
            reg.counter_fn("hostkv_fetches_total", lambda: hk.fetches)
            reg.counter_fn(
                "hostkv_spill_bytes_total", lambda: hk.spill_bytes_total
            )
            reg.counter_fn(
                "hostkv_fetch_bytes_total", lambda: hk.fetch_bytes_total
            )
            reg.counter_fn(
                "hostkv_evictions_total", lambda: hk.host_evictions
            )
            reg.gauge_fn(
                "hostkv_pages_resident", lambda: hk.pages_resident
            )
            reg.gauge_fn("hostkv_pages_capacity", lambda: hk.capacity)
        # Mesh geometry. The registry has no label support, so the shape
        # label rides an info-style gauge (value pinned to 1.0, shape in
        # the name) next to the numeric per-axis gauges; an unsharded
        # engine reports 1/1/0 under serving_mesh_1x1_info.
        reg.gauge_fn("data_axis_size", lambda: self._data_size)
        reg.gauge_fn("model_axis_size", lambda: self._model_size)
        reg.gauge_fn(
            "sharded_program_count", lambda: self._sharded_programs
        )
        reg.gauge_fn(f"mesh_{self.mesh_fingerprint}_info", lambda: 1.0)
        if self.goodput is not None:
            self.goodput.register_into(reg)
        if self.xla is not None:
            self.xla.register_into(reg)
        if self.sentinel is not None:
            self.sentinel.register_into(reg)
        if self.timeseries is not None:
            ts = self.timeseries
            reg.gauge_fn(
                "timeseries_series",
                lambda: float(len(ts.series_names())),
                help="Series tracked by the in-process TSDB",
            )
            reg.gauge_fn(
                "timeseries_memory_bytes",
                lambda: float(ts.memory_bytes()),
                help="Bounded TSDB retained-sample memory estimate",
            )
        if self.regress is not None:
            # Late-bound through the engine attribute (not the instance)
            # so a bench/test can swap in a differently-tuned detector
            # before the first step without orphaning the metrics.
            reg.counter_fn(
                "perf_regressions_total",
                lambda: float(self.regress.alerts),
                help="Sustained perf-level shifts detected by CUSUM",
            )
            reg.gauge_fn(
                "perf_regression_firing",
                lambda: float(self.regress.firing),
                help="1 after a perf regression until acknowledged",
            )
        if self.roofline is not None:
            self.roofline.register_into(reg)
        if self.flight.enabled:
            fl = self.flight
            reg.counter_fn(
                "flight_events_recorded_total",
                lambda: fl.recorded,
                help="Events appended to the flight-recorder ring",
            )
            reg.counter_fn(
                "flight_events_dropped_total",
                lambda: fl.dropped,
                help="Events that fell off the back of the ring",
            )
            reg.counter_fn(
                "flight_dumps_total",
                lambda: fl.dumps,
                help="Postmortem dumps written",
            )
        return reg

    # Pool accessors: the target pool keeps its historical ``self.cache``
    # name (the plain-engine hot path reads/writes it directly); the draft
    # pool exists only on speculative engines.

    @property
    def cache(self):
        return self.pools["target"]

    @cache.setter
    def cache(self, value):
        self.pools["target"] = value

    @property
    def draft_cache(self):
        return self.pools["draft"]

    @draft_cache.setter
    def draft_cache(self, value):
        self.pools["draft"] = value

    # ------------------------------------------------------------- compiled
    #
    # Every factory below branches once on ``self.mesh``: unsharded engines
    # get the EXACT jit call they always had (the bitwise guarantee is the
    # absence of any new annotation, not a (1,1) fast path), meshed engines
    # get the same trace wrapped in explicit in/out shardings — params
    # under SERVING_PARAM_RULES, pools under KV_POOL_SPEC, every
    # host-staged operand and sampled output replicated. Donated caches
    # keep their sharding on the way out, so device state never migrates
    # after init. Each sharded compile bumps ``_sharded_programs`` (a
    # registry gauge): lazily-built programs surface in obs exactly when
    # they start existing.

    def _ledgered(self, name, fn):
        """Route one compiled program through the XLA ledger when device
        accounting is on; the identity otherwise (the bitwise/fast-path
        guarantee is the absence of any wrapper, not a cheap wrapper)."""
        if self.xla is None:
            return fn
        return self.xla.wrap(name, fn)

    def _sharded_jit(self, run, *, donate, in_shardings, out_shardings):
        self._sharded_programs += 1
        return jax.jit(
            run,
            donate_argnums=donate,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
        )

    @functools.cached_property
    def _decode_step(self):
        """THE batched decode program: one compile for the engine's
        lifetime. Greedy and sampled rows coexist via a per-slot temperature
        vector (0 = greedy); ``prev``/``use_prev`` splice the previous
        step's device-resident samples in as inputs so overlapped slots
        never wait on a host readback. ``keys`` is the staged
        ``[max_slots, 3]`` operand of base keys and token indices; the
        per-step keys are folded here, in the program (see the module
        docstring). ``bias`` is the fixed-shape
        ``[max_slots, vocab]`` additive logit operand carrying
        per-request logit-bias and grammar-mask rows — always present
        (all-zeros when no row has mods, a cached device constant so the
        common path stages no extra bytes), so mods arrive as data and
        the program NEVER recompiles for them."""
        row_sample = make_row_sampler(self._top_k, self._top_p)

        def run(params, cache, tokens, prev, use_prev, tables, lens, temps,
                keys, bias, *window):
            tok = jnp.where(use_prev > 0, prev, tokens)
            last_logits, cache, *routing = self._forward(
                params, cache, tok[:, None],
                block_tables=tables, seq_lens=lens,
                **self._decode_state_kw(tables, lens),
                # A window group's short tables (none: the model has none).
                **({"window_tables": window[0]} if window else {}),
            )
            nxt = row_sample(last_logits, temps, fold_row_keys(keys), bias)
            return (nxt, cache, *routing)

        # The fused-kernel decode compiles under its own ledger name so the
        # roofline attributes the before/after to two distinct programs
        # (both keep the "decode_step" prefix the analytic FLOPs model and
        # roofline tagging key on).
        name = "decode_step_paged" if self.paged_kernel else "decode_step"
        if self.mesh is None:
            return self._ledgered(
                name, jax.jit(run, donate_argnums=(1,))
            )
        rep = self._replicated
        pool = self._pool_shardings["target"]
        # prev is device-resident feedback: it comes back replicated (out
        # sharding below) and is consumed replicated, so the overlapped
        # splice never adds a collective.
        return self._ledgered(
            name,
            self._sharded_jit(
                run,
                donate=(1,),
                in_shardings=(
                    self._param_shardings, pool, rep, rep, rep, rep, rep,
                    rep, rep, rep,
                ),
                out_shardings=(rep, pool),
            ),
        )

    def _forward(self, params, cache, tokens, **kw):
        """``decode_token_step`` on the decode model: ``(last_logits,
        cache)``, and for a model with routed layers a third result, the
        layers' routing counts ``[routed layers, n_experts]`` in layer
        order."""
        wanted = ("routing",) * bool(self.routed_layers) + (
            "selection",) * self.reads.selection
        if not wanted:
            return decode_token_step(
                self.decode_model, params, cache, tokens, **kw
            )
        last_logits, cache, sown = decode_token_step(
            self.decode_model, params, cache, tokens,
            mutable=("cache",) + wanted, **kw,
        )

        def by_layer(collection, *path):
            found = sown.get(collection, {})
            out = []
            for i in range(self.decode_model.n_layers):
                leaf = found.get(f"block_{i}")
                for key in path:
                    leaf = None if leaf is None else leaf.get(key)
                if leaf is not None:
                    out.append(leaf[0])
            return out

        extras = ()
        if self.routed_layers:
            extras += (jnp.stack(by_layer("routing", "experts", "counts")),)
        # Only a decode program's sparse layers write their selection (a
        # prefill piece masks and keeps no list).
        chosen = by_layer("selection", "mla", "positions")
        if chosen:
            extras += (jnp.stack(chosen),)
        return (last_logits, cache) + extras

    def _flush_routing(self) -> None:
        """Write the ``moe.routing`` instant of the step whose counts are
        due (module docstring): its programs have finished by now."""
        due, self._routing_due = self._routing_due, None
        if due is None or not due[1]:
            return
        step, arrays = due
        counts = np.stack([np.asarray(a) for a in arrays]).astype(np.int64)
        lo, hi = self.decode_model.experts_held or (0, counts.shape[-1])
        held = counts[..., lo:hi]  # [programs, layers, held experts]
        # The held experts' counts ARE the grouped products' group sizes:
        # the rows the kernel multiplied, by its own tiling rule.
        computed = rows_computed(held) if self.moe_product != "xla" else 0
        self.moe_pairs_held += int(held.sum())
        self.moe_rows_computed += computed
        self.tracer.instant(
            "moe.routing", step=step, moe_programs=len(arrays),
            moe_pairs_held=int(held.sum()), moe_rows_computed=computed,
            moe_pairs_absent=int(counts.sum() - held.sum()),
            moe_experts_hit=int((held > 0).sum()),
            moe_tokens_per_expert_max=int(held.max(axis=-1).sum()),
            moe_tokens_per_expert_mean=float(held.mean(axis=-1).sum()),
        )

    def _state_bytes(self) -> int:
        """Recurrent state held by the requests that own a slot."""
        return self.state_bytes_per_slot * len(self.scheduler.running)

    def _decode_state_kw(self, tables, lens) -> dict:
        """What the batched decode program tells a model with recurrent or
        routed layers (nothing to any other): row ``r`` carries slot ``r``'s
        state, and is routed to experts and counted, iff the row is in the
        dispatched group, which is iff its staged block table is not the
        zeroed one. And what it tells its decode kernels of its rows
        (:meth:`DecodeReads.operands`), worked out here once for all its
        layers."""
        kw = {}
        if self.state_layers or self.routed_layers:
            rows = jnp.arange(self.max_slots, dtype=jnp.int32)
            kw["state_slots"] = jnp.where(tables[:, 0] != NULL_PAGE, rows, -1)
        kw.update(self.reads.operands(tables, lens))
        return kw

    def _note_state_reset(self, slot: int, req: Request) -> None:
        """A row at position 0 is about to run: its state starts from
        zeros (the mixer does it; this is the count and the trace)."""
        self.state_resets += 1
        if self.tracer.enabled:
            self.tracer.instant(
                "state.reset", slot=slot, request=req.req_id,
                cause="preempt" if req.preempt_count else "admit",
            )

    def _prefill_step(self, width: int):
        """The prefill program of one width: ``[1, width]`` tokens of which
        the first ``valid`` are a piece of a prompt and the rest padding
        the model leaves out of everything (module docstring, item 3).
        Returns only the updated cache, so XLA prunes the LM head from the
        program. With recurrent layers the program takes one more operand:
        the slot whose state the piece carries on. A builder, not a store:
        :attr:`_prefill_programs` keeps what it returns."""

        def run(params, cache, tokens, table, length, valid, *more):
            # With a window group its short table, a piece's pages wide,
            # comes first; with recurrent layers the slot comes last.
            state_kw = {}
            if self.window_group is not None:
                state_kw["window_tables"], *more = more
            if more:
                state_kw["state_slots"] = more[0]
            _, cache, *extras = self._forward(
                params, cache, tokens, block_tables=table, seq_lens=length,
                valid_lens=valid, **state_kw,
            )
            # The routing counts; what a one-token piece of a sparse layer
            # selected is nobody's to read.
            return (cache, extras[0]) if self.routed_layers else cache

        name = f"prefill_step_c{width}"
        if self.mesh is None:
            return self._ledgered(name, jax.jit(run, donate_argnums=(1,)))
        rep = self._replicated
        pool = self._pool_shardings["target"]
        return self._ledgered(
            name,
            self._sharded_jit(
                run,
                donate=(1,),
                in_shardings=(
                    self._param_shardings, pool, rep, rep, rep, rep
                ),
                out_shardings=pool,
            ),
        )

    def _prefill_width(self, tokens: int) -> int:
        """The width of the program a piece of ``tokens`` tokens runs in:
        the next whole number of granules."""
        granule = self.scheduler.prefill_granule
        return -(-tokens // granule) * granule

    @functools.cached_property
    def _prefill_programs(self) -> Dict[int, tuple]:
        """``width -> (target program, draft program or None)`` for every
        width a piece can have: ``g, 2g, ..., max_prefill_chunk``. Built
        when the first piece asks for one, and WHOLE: a deployment's first
        long prompt reaches only the widest program, and every other width
        would compile under the first request that needs it. So each
        program is also run here once, on an all-null table with valid
        length 0 and no slot, which writes the null page alone and touches
        no state, and leaves it compiled for the operands a real piece
        brings: after the first prefill no prompt length compiles anything.
        Eight programs at a cap of 512; one at any cap up to 64. A cap above
        1,024 only lengthens this pass by a compile a width (32 of them at
        2,048); nothing here is evicted."""
        granule = self.scheduler.prefill_granule
        zero = jnp.asarray([0], jnp.int32)
        rest = (  # the null table, start 0, valid length 0
            jnp.asarray(np.zeros((1, self.pages_per_seq), np.int32)),
            zero, zero,
        )
        no_slot = (jnp.asarray([-1], jnp.int32),) if self.state_layers else ()
        if self.window_group is not None:  # its null short table goes first
            no_slot = (jnp.asarray(np.zeros(
                (1, self.window_group.piece_pages), np.int32)),) + no_slot
        programs = {}
        with process_tracer().setup_phase(
            "engine.build_prefill_programs"
        ) as built:
            for width in range(
                granule, self.scheduler.max_prefill_chunk + 1, granule
            ):
                target = self._prefill_step(width)
                draft = (
                    self._draft_prefill_step(width)
                    if self.speculative else None
                )
                tokens = jnp.asarray(np.zeros((1, width), np.int32))
                out = target(self.params, self.cache, tokens, *rest, *no_slot)
                self.cache = out[0] if self.routed_layers else out
                if draft is not None:
                    self.draft_cache = draft(
                        self.draft_params, self.draft_cache, tokens, *rest
                    )
                programs[width] = (target, draft)
            built.note(programs=len(programs), widths=sorted(programs))
        return programs

    def _prefill_piece(self, slot: int, tokens: int) -> None:
        """Run one planned prefill piece, ``tokens`` tokens of ``slot``'s
        request from its first uncached one: ONE program (and the draft
        pool's twin), of the next width, the piece padded to it on the
        host. The one prefill loop body of both step bodies."""
        req = self.scheduler.slots[slot]
        start = req.len_cached
        width = self._prefill_width(tokens)
        if self._acct is not None and req.rework_until > start:
            self._note_rework(req, start, tokens)
        target, draft = self._prefill_programs[width]
        group = self.window_group
        with self._phase(
            "prefill.chunk", tokens=tokens, start=start, width=width,
            **self.reads.prefill_args(
                width, start, tokens, self.tracer.enabled),
            # What the piece holds at its widest: its window's pages and
            # its own, before it gives the former back.
            **({} if group is None
               else {"window_pages": len(req.window_table.pages)}),
        ):
            tok = np.zeros((1, width), np.int32)
            tok[0, :tokens] = req.tokens[start : start + tokens]
            table = req.table.as_row(self.pages_per_seq)[None]
            if self.xla is not None:
                # Piece, table, start and valid length, staged once a pool.
                self.xla.count_h2d(
                    (tok.nbytes + table.nbytes + 8) * len(self.pools.names)
                )
            operands = (
                jnp.asarray(tok), jnp.asarray(table),
                jnp.asarray([start], jnp.int32),
                jnp.asarray([tokens], jnp.int32),
            )
            # Adapter rows prefill under their merged weights — K/V
            # written under base params would poison every decode step
            # that attends to it.
            ms = req.mods
            params = (
                self.adapters.params_for(ms.adapter)
                if ms is not None and ms.adapter is not None
                else self.params
            )
            state_slot = ()
            if group is not None:
                state_slot = (jnp.asarray(req.window_table.as_row(
                    group.piece_pages, group.first_page(start))[None]),)
            if self.state_layers:
                state_slot += (jnp.asarray([slot], jnp.int32),)
                if start == 0:
                    self._note_state_reset(slot, req)
            out = target(params, self.cache, *operands, *state_slot)
            if self.routed_layers:
                self.cache, counts = out
                self.routing_counts.append(counts)
            else:
                self.cache = out
            if draft is not None:
                self.draft_cache = draft(
                    self.draft_params, self.draft_cache, *operands
                )
        self.prefill_programs += 1
        self.prefill_tokens += tokens
        self.prefill_width += width
        self.scheduler.note_prefilled(slot, tokens)

    @functools.cached_property
    def _copy_page(self):
        """Copy one physical page across every layer's K/V pool — the
        device half of copy-on-write. Page ids are traced scalars, so this
        compiles exactly once (per pool when meshed: pools differ in
        sharding pytree, so the mesh path returns a pool-name -> program
        mapping, which :meth:`PagePoolGroup.copy_page` accepts)."""

        def run(cache, src, dst):
            return jax.tree_util.tree_map(
                lambda pool: pool.at[dst].set(pool[src]), cache
            )

        if self.mesh is None:
            return self._ledgered(
                "copy_page", jax.jit(run, donate_argnums=(0,))
            )
        rep = self._replicated
        return {
            name: self._ledgered(
                f"copy_page_{name}",
                self._sharded_jit(
                    run,
                    donate=(0,),
                    in_shardings=(self._pool_shardings[name], rep, rep),
                    out_shardings=self._pool_shardings[name],
                ),
            )
            for name in self.pools.names
        }

    @functools.cached_property
    def _spill_page(self):
        """Gather one physical page across every layer of a pool — the
        device half of a host-tier spill. The cache is NOT donated (the
        pools live on); the gathered page materializes host-side later,
        in :meth:`HostPageTier.drain_spills`, so eviction never blocks
        on a d2h sync. Meshed engines replicate the gathered page so the
        host drain reads one contiguous buffer per leaf."""

        def run(cache, src):
            return jax.tree_util.tree_map(lambda pool: pool[src], cache)

        if self.mesh is None:
            return self._ledgered("spill_page", jax.jit(run))
        rep = self._replicated
        return {
            name: self._ledgered(
                f"spill_page_{name}",
                self._sharded_jit(
                    run,
                    donate=(),
                    in_shardings=(self._pool_shardings[name], rep),
                    out_shardings=rep,
                ),
            )
            for name in self.pools.names
        }

    @functools.cached_property
    def _fetch_pages(self):
        """Write a BATCH of spilled pages' host K/V back into every
        layer of a pool — ONE program dispatch per pool per step, never
        per page (per-page dispatch overhead would eat the saved
        prefill on small pages). Same device-resident dispatch trick as
        the overlapped step loop: the write is dispatched before the
        step's prefill/decode, and the cache data dependency orders it
        ahead of any program that reads the destination pages, so the
        fetch overlaps ongoing decode instead of stalling it. Callers
        pad the batch to power-of-two buckets with NULL-page writes
        (zeros to page 0, which no real sequence reads) so jit retraces
        stay bounded."""

        def run(cache, chunks, dsts):
            return jax.tree_util.tree_map(
                lambda pool, c: pool.at[dsts].set(c), cache, chunks
            )

        if self.mesh is None:
            return self._ledgered(
                "fetch_pages", jax.jit(run, donate_argnums=(0,))
            )
        rep = self._replicated
        return {
            name: self._ledgered(
                f"fetch_pages_{name}",
                self._sharded_jit(
                    run,
                    donate=(0,),
                    in_shardings=(self._pool_shardings[name], rep, rep),
                    out_shardings=self._pool_shardings[name],
                ),
            )
            for name in self.pools.names
        }

    def _gather_page(self, page: int):
        """HostPageTier's gather hook: slice ``page`` out of every pool
        as device arrays (async — materialized at drain time)."""
        src = jnp.asarray(page, jnp.int32)
        fn = self._spill_page
        per_pool = isinstance(fn, dict)
        return {
            name: (fn[name] if per_pool else fn)(self.pools[name], src)
            for name in self.pools.names
        }

    def _execute_fetches(self, fetches) -> None:
        """Stage every planned host-tier fetch h2d — batched into one
        program dispatch per pool — and unpin the host entries. Byte
        accounting mirrors the spill side: the tier counts the REAL
        fetched bytes in :meth:`HostPageTier.chunks` (bucket padding is
        excluded), and the same sum lands in the transfer ledger under
        the ``hostkv_fetch`` tag, so the two ledgers cross-check
        exactly."""
        tier = self.hostkv
        fn = self._fetch_pages
        per_pool = isinstance(fn, dict)
        staged = 0
        dsts: list = []
        per_pool_chunks = {name: [] for name in self.pools.names}
        for key, page, _parent, _tokens, _node in fetches:
            chunks = tier.chunks(key)
            dsts.append(page)
            for name, chunk in chunks.items():
                staged += sum(
                    c.nbytes
                    for c in jax.tree_util.tree_leaves(chunk)
                )
                per_pool_chunks[name].append(chunk)
            tier.unpin(key)
            self.prefix_cache.fetch_pending.discard(page)
        # Pad to the next power-of-two bucket: the padding rows write
        # zeros to the NULL page (reserved, never read by a live
        # sequence), so every batch size in a bucket shares one compile.
        bucket = 1
        while bucket < len(dsts):
            bucket *= 2
        pad = bucket - len(dsts)
        dst_arr = jnp.asarray(dsts + [NULL_PAGE] * pad, jnp.int32)
        for name in self.pools.names:
            stacked = jax.tree_util.tree_map(
                lambda *leaves: np.stack(leaves),
                *per_pool_chunks[name],
            )
            if pad:
                stacked = jax.tree_util.tree_map(
                    lambda s: np.concatenate(
                        [s, np.zeros((pad,) + s.shape[1:], s.dtype)]
                    ),
                    stacked,
                )
            run = fn[name] if per_pool else fn
            self.pools[name] = run(self.pools[name], stacked, dst_arr)
        if staged and self.xla is not None:
            self.xla.count_h2d(staged, tag="hostkv_fetch")

    def _draft_prefill_step(self, width: int):
        """Draft-pool twin of :meth:`_prefill_step`: every prefill piece
        runs through BOTH models so the draft pool holds valid K/V for
        exactly the positions the target pool does — including
        trie-adopted pages, which were prefilled by both models when first
        written and so stay adoptable in lockstep."""

        def run(draft_params, draft_cache, tokens, table, length, valid):
            _, draft_cache = decode_token_step(
                self.draft_decode_model, draft_params, draft_cache, tokens,
                block_tables=table, seq_lens=length, valid_lens=valid,
            )
            return draft_cache

        name = f"draft_prefill_step_c{width}"
        if self.mesh is None:
            return self._ledgered(name, jax.jit(run, donate_argnums=(1,)))
        rep = self._replicated
        pool = self._pool_shardings["draft"]
        return self._ledgered(
            name,
            self._sharded_jit(
                run,
                donate=(1,),
                in_shardings=(
                    self._draft_param_shardings, pool, rep, rep, rep, rep
                ),
                out_shardings=pool,
            ),
        )

    @functools.cached_property
    def _spec_step(self):
        """THE speculative round program — one compile for the engine's
        lifetime, batched over all slots like :meth:`_decode_step`:

        1. gamma single-token DRAFT steps (``fori_loop``) sample/argmax a
           proposal chunk per row, writing draft K/V at positions
           ``lens..lens+gamma-1`` and recording each step's filtered draft
           distribution q for the acceptance ratio;
        2. ONE gamma-wide chunked TARGET forward over
           ``[x_t, d_0..d_{gamma-2}]`` at the same positions scores every
           proposal (logits[:, j] decides position ``lens+j+1``);
        3. per-row acceptance: greedy rows keep proposals matching the
           target argmax; sampled rows accept d_i iff
           ``u_i * q(d_i) < p(d_i)`` and resample the first rejection from
           the residual ``max(p - q, 0)`` (exact target law, same rule as
           offline ``speculative_generate``).

        Returns ``(emitted [S, gamma], n_accepted [S])`` plus both updated
        pools; row s's round contributes ``min(n_accepted[s]+1, gamma)``
        tokens, ``emitted[s, :that]``. K/V past a row's emitted count is
        rejected garbage in BOTH pools and needs no cleanup: reads mask
        positions >= seq_len and the next round overwrites before
        attending. The round's key is folded in the program from the
        staged ``[max_slots, 3]`` operand (base key, token index), as in
        :meth:`_decode_step`, and per-round sub-draws derive from it:
        draft step i folds i, acceptance uniforms fold gamma, the
        residual draw folds gamma+1 — batch-composition independent, like
        everything else about sampling here."""
        top_k, top_p = self._top_k, self._top_p
        gamma = self.gamma
        n_slots = self.max_slots
        vocab = self.decode_model.vocab_size

        def filtered(logits, temps):
            # The distribution actually sampled from, f32 for the
            # acceptance-ratio arithmetic (mirrors offline speculative.py).
            safe_t = jnp.where(temps > 0, temps, 1.0)
            shaped = safe_t.reshape((-1,) + (1,) * (logits.ndim - 1))
            return jax.nn.softmax(
                truncate_logits(logits / shaped, top_k, top_p).astype(
                    jnp.float32
                ),
                axis=-1,
            )

        def run(params, draft_params, cache, draft_cache, tokens, tables,
                lens, temps, keys):
            rows = jnp.arange(n_slots)
            keys = fold_row_keys(keys)

            def fold_all(i):
                return jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                    keys, i
                )

            # --- draft phase: propose gamma tokens per row -------------
            buf = jnp.zeros((n_slots, gamma + 1), jnp.int32)
            buf = buf.at[:, 0].set(tokens)
            qbuf = jnp.zeros((n_slots, gamma, vocab), jnp.float32)

            def draft_body(i, carry):
                buf, qbuf, dcache = carry
                cur = jax.lax.dynamic_slice_in_dim(buf, i, 1, axis=1)
                logits, dcache = decode_token_step(
                    self.draft_decode_model, draft_params, dcache, cur,
                    block_tables=tables, seq_lens=lens + i,
                )
                q = filtered(logits, temps)  # [S, V]
                sampled = jax.vmap(jax.random.categorical)(
                    fold_all(i), jnp.log(q)
                ).astype(jnp.int32)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                nxt = jnp.where(temps > 0, sampled, greedy)
                buf = buf.at[:, i + 1].set(nxt)
                qbuf = jax.lax.dynamic_update_slice_in_dim(
                    qbuf, q[:, None, :], i, axis=1
                )
                return buf, qbuf, dcache

            buf, qbuf, draft_cache = jax.lax.fori_loop(
                0, gamma, draft_body, (buf, qbuf, draft_cache)
            )

            # --- verify phase: one chunked target forward --------------
            chunk = buf[:, :gamma]       # [x_t, d_0 .. d_{gamma-2}]
            proposals = buf[:, 1:]       # [d_0 .. d_{gamma-1}]
            t_logits, cache = decode_chunk_step(
                self.decode_model, params, cache, chunk,
                block_tables=tables, seq_lens=lens,
            )
            greedy_t = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
            p = filtered(t_logits, temps)  # [S, gamma, V]
            px = jnp.take_along_axis(
                p, proposals[..., None], axis=-1
            )[..., 0]
            qx = jnp.take_along_axis(
                qbuf, proposals[..., None], axis=-1
            )[..., 0]
            u = jax.vmap(lambda k: jax.random.uniform(k, (gamma,)))(
                fold_all(gamma)
            )
            # u < min(1, px/qx)  <=>  u*qx < px (q(x) > 0 a.s.).
            accept = jnp.where(
                temps[:, None] > 0, u * qx < px, proposals == greedy_t
            )
            n_acc = jnp.sum(
                jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1
            )
            # Correction at column ni = min(n_acc, gamma-1). Fully
            # accepted rows route back to their own last proposal via the
            # n_acc > ni select (no bonus token — matches offline).
            ni = jnp.minimum(n_acc, gamma - 1)
            p_n = jnp.take_along_axis(p, ni[:, None, None], axis=1)[:, 0]
            q_n = jnp.take_along_axis(qbuf, ni[:, None, None], axis=1)[:, 0]
            residual = jnp.maximum(p_n - q_n, 0.0)
            has_mass = jnp.sum(residual, axis=-1, keepdims=True) > 0
            res_dist = jnp.where(has_mass, residual, p_n)
            resampled = jax.vmap(jax.random.categorical)(
                fold_all(gamma + 1), jnp.log(res_dist)
            ).astype(jnp.int32)
            greedy_repl = jnp.take_along_axis(
                greedy_t, ni[:, None], axis=1
            )[:, 0]
            replacement = jnp.where(temps > 0, resampled, greedy_repl)
            kept = jnp.take_along_axis(proposals, ni[:, None], axis=1)[:, 0]
            corrected = jnp.where(n_acc > ni, kept, replacement)
            emitted = proposals.at[rows, ni].set(corrected)
            return emitted, n_acc, cache, draft_cache

        if self.mesh is None:
            return self._ledgered(
                "spec_step", jax.jit(run, donate_argnums=(2, 3))
            )
        rep = self._replicated
        pool = self._pool_shardings["target"]
        draft_pool = self._pool_shardings["draft"]
        return self._ledgered(
            "spec_step",
            self._sharded_jit(
                run,
                donate=(2, 3),
                in_shardings=(
                    self._param_shardings, self._draft_param_shardings,
                    pool, draft_pool, rep, rep, rep, rep, rep,
                ),
                out_shardings=(rep, rep, pool, draft_pool),
            ),
        )

    # ----------------------------------------------------------------- API

    def register_adapter(
        self,
        name: str,
        adapters,
        *,
        rank: int,
        alpha: Optional[float] = None,
    ) -> None:
        """Register a named LoRA adapter (a ``training/lora.py`` low-rank
        tree) for per-request multiplexing. Merging happens eagerly here
        — the merge jit compiles NOW, so register every adapter before
        ``arm_recompile_sentinel()`` and the sentinel stays zero at
        steady state no matter how requests mix adapters."""
        if self.mesh is not None:
            raise ValueError(
                "adapter mods are not supported on meshed engines"
            )
        self.adapters.register(name, adapters, rank=rank, alpha=alpha)

    def submit(
        self,
        prompt: Sequence[int],
        params: Optional[SamplingParams] = None,
        metadata: Optional[dict] = None,
        *,
        tenant_id: str = "anon",
        mods: Optional[Mods] = None,
        trace_id: Optional[str] = None,
    ) -> int:
        """Queue one request; returns its id. Raises
        :class:`~.admission.QueueFull` (backpressure),
        :class:`~.admission.RequestTooLong` (can never fit), or
        :class:`~.admission.EngineDraining` (drain/close in progress) —
        admission is decided NOW, not at first schedule, and counts the
        currently-cached prefix: a shared-prompt request costs only its
        uncached tail of prefill work against the queue-token budget.
        ``tenant_id`` is the typed tenancy key (fair-share, quotas,
        per-tenant SLOs, preserved across drain/restore); ``metadata``
        remains a tenant-opaque JSON-serializable dict carried through
        scheduling (and the elastic snapshot) untouched. ``mods`` is an
        optional :class:`~.mods.Mods` spec (logit bias / grammar /
        adapter); device mods are refused on speculative engines (the
        fused verify program has no bias operand) and adapter mods on
        meshed engines (merged trees are placed unsharded). ``trace_id``
        is the fleet-wide trace identity a layer above minted (front door
        / router) — stamped into the request span and flight events so
        the engine's slice of work joins the merged fleet trace."""
        if self._server is None:
            return self._submit_impl(
                prompt, params, metadata, tenant_id, mods, trace_id
            )
        with self.registry.lock:
            return self._submit_impl(
                prompt, params, metadata, tenant_id, mods, trace_id
            )

    def _submit_impl(
        self,
        prompt: Sequence[int],
        params: Optional[SamplingParams],
        metadata: Optional[dict],
        tenant_id: str = "anon",
        mods: Optional[Mods] = None,
        trace_id: Optional[str] = None,
    ) -> int:
        params = params or SamplingParams()
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        mod_state: Optional[ModState] = None
        if mods is not None and mods.device_mods:
            if self.speculative:
                raise ValueError(
                    "logit-bias/grammar/adapter mods are not supported "
                    "on speculative engines (stop_sequences are)"
                )
            if mods.adapter is not None:
                if self.mesh is not None:
                    raise ValueError(
                        "adapter mods are not supported on meshed engines"
                    )
                if mods.adapter not in self.adapters:
                    raise KeyError(
                        f"unknown adapter {mods.adapter!r} — call "
                        "register_adapter() first"
                    )
            mod_state = ModState(mods, self.vocab_size)
        cached = 0
        if self.prefix_cache is not None and prompt:
            cached = self.prefix_cache.peek(prompt)
        self.admission.check(
            len(prompt), params, self.scheduler.num_waiting,
            cached_tokens=cached,
            queued_uncached_tokens=sum(
                r.est_uncached for r in self.scheduler.waiting
            ),
            tenant_id=tenant_id,
            trace_id=trace_id,
        )
        req = Request(
            req_id=self._next_id,
            prompt=prompt,
            params=params,
            submit_time=time.perf_counter(),
            est_uncached=max(0, len(prompt) - 1 - cached),
            metadata=metadata,
            tenant_id=tenant_id,
            mods=mod_state,
            trace_id=trace_id,
        )
        self._next_id += 1
        self.requests[req.req_id] = req
        self._keys[req.req_id] = host_prng_key(params.seed)
        if self.tracer.enabled:
            extra = {"trace_id": trace_id} if trace_id is not None else {}
            self.tracer.request_begin(
                req.req_id,
                prompt_len=len(prompt),
                max_new_tokens=params.max_new_tokens,
                cached_tokens_at_submit=cached,
                **extra,
            )
            if trace_id is not None:
                # Receive the fleet flow arrow on the engine's request lane.
                self.tracer.flow("t", trace_id, _PID_REQUESTS)
        self.scheduler.add(req)
        return req.req_id

    def _resolve_inflight(self) -> List[int]:
        """Read back the outstanding decode dispatch (the ONE blocking
        device sync — under overlap it lands while the next step computes),
        fill in sampled tokens, retire what finished."""
        nxt, slots, reqs = self._inflight
        self._inflight = None
        return self._resolve_rows(nxt, slots, reqs)

    def _resolve_rows(
        self, nxt, slots: List[int], reqs: List[Request]
    ) -> List[int]:
        """Resolve one decode dispatch's sampled tokens (async inflight
        or an in-step sync mod group): fill values, retire finishers."""
        with self._phase("readback.wait", bytes=nxt.nbytes):
            nxt_host = np.asarray(nxt)
        if self.xla is not None:
            self.xla.count_d2h(nxt_host.nbytes)
        now = time.perf_counter()
        finished: List[int] = []
        with self._phase("readback.resolve", rows=len(slots)) as span:
            for slot, req in zip(slots, reqs):
                done = self.scheduler.resolve_decoded(
                    req, int(nxt_host[slot]), now=now
                )
                if self.tracer.enabled:
                    self.tracer.request_event(
                        req.req_id, "decode_token",
                        n_generated=req.n_generated,
                    )
                if done is not None:
                    self.scheduler.retire(done, now=now)
                    self.metrics.observe_finished(done)
                    self._keys.pop(done.req_id, None)
                    finished.append(done.req_id)
            span.note(finished=len(finished))
        return finished

    def _stage_row_keys(self, slots: List[int]) -> None:
        """Write each row's base key and ``n_issued`` (the index of the
        token it is about to draw, pending ones counted) into the staged
        key operand: plain NumPy writes under one ``dispatch.key`` slice a
        launch, which times all that the keys cost the host."""
        with self._phase("dispatch.key", rows=len(slots)):
            for slot in slots:
                req = self.scheduler.slots[slot]
                staged = self._stage_keys[slot]
                staged[:2] = self._keys[req.req_id]
                staged[2] = req.n_issued

    def _dispatch_decode(self, slots: List[int], params, prev):
        """Stage and run THE decode program for ``slots``. Rows outside
        the group stage a zeroed block table and length, so their masked
        K/V writes land in the null page — per-group dispatch commits
        state for its own rows only, which is what lets one step issue
        the base program plus per-adapter groups against one cache. The
        sampling keys are staged as host data (:meth:`_stage_row_keys`)
        and folded inside the program: nothing here reads from the device,
        so the dispatch never waits for the program in flight."""
        self._stage_tables.fill(0)
        self._stage_lens.fill(0)
        self._stage_use_prev.fill(0)
        group = self.window_group
        if group is not None:
            self._stage_window_tables.fill(0)
        bias = None
        for slot in slots:
            req = self.scheduler.slots[slot]
            pos = req.len_cached
            tok = req.tokens[pos]
            if tok == PENDING_TOKEN:
                # Input is last step's still-in-flight sample: select it
                # device-side from ``prev``.
                self._stage_use_prev[slot] = 1
                self._stage_tokens[slot] = 0
            else:
                self._stage_tokens[slot] = tok
            self._stage_tables[slot] = req.table.as_row(self.pages_per_seq)
            self._stage_lens[slot] = pos
            if group is not None:
                self._stage_window_tables[slot] = req.window_table.as_row(
                    group.decode_pages, group.first_page(pos))
            if pos == 0 and self.state_layers:  # a one-token prompt
                self._note_state_reset(slot, req)
            self._stage_temps[slot] = req.params.temperature
            row = req.mods.bias_row() if req.mods is not None else None
            if row is not None:
                if bias is None:
                    bias = self._stage_bias
                    bias.fill(0.0)
                bias[slot] = row
        self._stage_row_keys(slots)
        self.reads.note(
            self._stage_tables, self._stage_lens, sorted(slots), self.tracer
        )
        staged = (
            self._stage_tokens.nbytes
            + self._stage_use_prev.nbytes
            + self._stage_tables.nbytes
            + self._stage_lens.nbytes
            + self._stage_temps.nbytes
            + self._stage_keys.nbytes
        )
        if bias is not None:
            staged += bias.nbytes
        with self._phase("dispatch.stage", rows=len(slots), bytes=staged):
            if self.xla is not None:
                self.xla.count_h2d(staged)
            # No modded rows: reuse the zeros device constant — the bias
            # operand costs the common path nothing.
            bias_arr = self._zero_bias if bias is None else _staged(bias)
            decode_step = self._decode_step
            tokens = _staged(self._stage_tokens)
            use_prev = _staged(self._stage_use_prev)
            tables = _staged(self._stage_tables)
            lens = _staged(self._stage_lens)
            temps = _staged(self._stage_temps)
            keys = _staged(self._stage_keys)
            window = (
                () if group is None
                else (_staged(self._stage_window_tables),)
            )
        with self._phase("dispatch.launch"):
            nxt, self.cache, *extras = decode_step(
                params, self.cache, tokens, prev, use_prev, tables, lens,
                temps, keys, bias_arr, *window,
            )
        if self.reads.selection:
            self.selected_positions.append(extras.pop())
        self.routing_counts.extend(extras)
        return nxt

    def _end_step_trace(self, plan) -> None:
        """Close the tracer's step slice with the per-step gauges: batch
        composition, token-budget utilization, page states, queue pressure.
        Gauge computation happens ONLY here, behind ``tracer.enabled`` — a
        disabled engine never takes this branch."""
        cost = self.gamma if self.speculative else 1
        used = sum(chunk for _s, chunk in plan.prefill) + (
            len(plan.decode_slots) * cost
        )
        pages = self.allocator.counters()
        extra = {}
        if self.goodput is not None:
            # One counter track per trace: the goodput fraction as of the
            # PREVIOUS step's accounting (this step's feed lands after the
            # slice closes).
            extra["goodput_fraction"] = self.goodput.fraction()
        if self.xla is not None:
            # Host<->device transfer ledger as counter tracks: bytes staged
            # up / read back since the previous step's slice closed.
            dh2d, dd2h = self.xla.step_transfer_deltas()
            extra["bytes_h2d"] = dh2d
            extra["bytes_d2h"] = dd2h
            extra["live_buffer_bytes"] = self.xla.live_bytes
        if self.state_layers:
            extra["state_slots_in_use"] = len(self.scheduler.running)
            extra["state_bytes"] = self._state_bytes()
        if self.routed_layers:
            # The step before this one has been read back: its counts cost
            # no wait. This step's wait for the next trace (or a flush).
            self._flush_routing()
            self._routing_due = (self.tracer.step_index, self.routing_counts)
        extra.update(self.reads.end_step())
        extra.update(self._window_step())
        self.tracer.end_step(
            decode_rows=len(plan.decode_slots),
            prefill_programs=len(plan.prefill),
            prefill_tokens=sum(tokens for _s, tokens in plan.prefill),
            prefill_width=sum(
                self._prefill_width(tokens) for _s, tokens in plan.prefill
            ),
            budget_utilization=used / self.scheduler.token_budget,
            queue_depth=self.scheduler.num_waiting,
            running_requests=len(self.scheduler.running),
            pages_free=pages["pages_free"],
            pages_referenced=pages["pages_referenced"],
            pages_cached_idle=pages["pages_cached_idle"],
            **extra,
        )

    def _window_step(self) -> dict:
        """What a ``step`` slice says of the window group (nothing where the
        model has none): the pages its tables gave back in the step and hold
        at its end, the allocator's free count; and the step's
        ``window.free`` instant, if it freed."""
        group = self.window_group
        if group is None:
            return {}
        freed, trims = self._window_seen
        self._window_seen = group.pages_freed, group.trims
        freed, trims = group.pages_freed - freed, group.trims - trims
        if freed:
            self.tracer.instant("window.free", pages=freed, rows=trims)
        counts = group.allocator.counters()
        return {
            "window_pages_freed": freed,
            "window_pages_held": counts["pages_referenced"],
            "window_pages_free": counts["pages_free"],
        }

    def step(self) -> List[int]:
        """Run one engine iteration; returns ids of requests that FINISHED
        during it (under overlap, a finish surfaces on the step after its
        token was dispatched). A no-op (empty list) when nothing is queued,
        running, or in flight.

        With goodput accounting, an SLO monitor, a flight recorder, an XLA
        ledger, or an introspection server attached, the step is wrapped
        in wall-clock attribution (see :meth:`_account_step`) and — when a
        server is live — the registry lock, so scrapes only ever observe
        step boundaries; none of it touches device work or scheduling
        decisions, so outputs stay bitwise-identical (pinned by the
        obs-parity bench gate and the server parity test)."""
        if (
            self.goodput is None
            and self.slo is None
            and not self.flight.enabled
            and self.xla is None
            and self.timeseries is None
            and self._server is None
        ):
            return self._step_impl()
        with self.registry.lock:
            t0 = time.perf_counter()
            self._acct = {
                "plan": None, "rework": None, "emitted": 0, "proposed": 0,
                "phases": {},
            }
            try:
                finished = self._step_impl()
            finally:
                acct, self._acct = self._acct, None
            self._account_step(acct, time.perf_counter() - t0, finished)
            if self.xla is not None:
                self.xla.update_live_bytes()
            return finished

    def _account_step(self, acct, dt_s: float, finished: List[int]) -> None:
        """Post-step bookkeeping: feed the goodput tracker, append the
        flight-recorder step record, tick the SLO monitor."""
        plan = acct["plan"]
        prefill_tokens = decode_rows = 0
        if plan is not None:
            prefill_tokens = sum(chunk for _s, chunk in plan.prefill)
            decode_rows = len(plan.decode_slots)
        if self.speculative:
            decode_positions = acct["proposed"]
            emitted = acct["emitted"]
        else:
            decode_positions = emitted = decode_rows
        queue_depth = self.scheduler.num_waiting
        if self.goodput is not None:
            self.goodput.note_step(
                dt_s,
                prefill_tokens=prefill_tokens,
                decode_positions=decode_positions,
                emitted_tokens=emitted,
                spec_proposed=acct["proposed"],
                rework=acct["rework"],
                budget_used=prefill_tokens + decode_positions,
                token_budget=self.scheduler.token_budget,
                queue_depth=queue_depth,
            )
        if self.flight.enabled:
            self.flight.record(
                "step",
                step=self.metrics.engine_steps,
                dur_s=dt_s,
                prefill_tokens=prefill_tokens,
                decode_rows=decode_rows,
                emitted_tokens=emitted,
                queue_depth=queue_depth,
                running=len(self.scheduler.running),
                finished=len(finished),
            )
        if self.slo is not None:
            self.slo.tick()
        if self.timeseries is not None:
            tpot = dt_s / emitted if emitted > 0 else None
            derived = {
                "step_wall_seconds": dt_s,
                "decode_rows": float(decode_rows),
                "prefill_tokens": float(prefill_tokens),
                "tokens_per_sec": (emitted / dt_s) if dt_s > 0 else 0.0,
            }
            if tpot is not None:
                derived["tpot_step_seconds"] = tpot
            phases = acct.get("phases") or {}
            for name, spent in phases.items():
                derived[f"phase_{name}_seconds"] = spent
            # One tick samples every tracked registry counter/gauge (the
            # goodput fractions ride along as registry gauges) plus the
            # derived serving series above.
            self.timeseries.sample(**derived)
            if self.regress is not None:
                self.regress.observe(
                    step_wall_seconds=dt_s,
                    tpot_step_seconds=tpot,
                    decode_rows=decode_rows,
                    prefill_tokens=prefill_tokens,
                    phases=phases,
                )

    def _note_rework(self, req, start: int, chunk: int) -> None:
        """Charge the prefill positions below ``req.rework_until`` — K/V
        the engine had already computed before a preemption or restore —
        to the request's waste bucket. Called only while accounting."""
        rw = min(start + chunk, req.rework_until) - start
        if rw <= 0:
            return
        rework = self._acct["rework"]
        if rework is None:
            rework = self._acct["rework"] = {}
        rework[req.rework_kind] = rework.get(req.rework_kind, 0) + rw

    def _phase(self, name: str, **args):
        """Step-phase span: the tracer's phase slice, plus (when the
        accounting wrapper is active) per-phase wall-time accumulation
        into ``_acct["phases"]`` — the series the regression detector
        blames — and (when a chaos ``slow_program`` fault is armed) the
        injected stall, slept INSIDE the span so traces, phase series,
        and detector attribution all see the slowdown where it was
        injected. With no accounting and no armed perf fault this returns
        the tracer's own context, so the all-obs-off fast path stays one
        attribute lookup away from the original code. ``args`` (and what
        the body adds through the context's ``note``) go into the slice."""
        plan = chaos.get_plan()
        stall = (
            plan.serving_stall(name)
            if plan is not None and plan.has_perf_faults()
            else 0.0
        )
        if self._acct is None and stall <= 0.0:
            return self.tracer.phase(name, **args)
        return _PhaseSpan(self, name, stall, args)

    def _step_impl(self) -> List[int]:
        chaos.on_serving_phase(
            "step", queue_depth=self.scheduler.num_waiting
        )
        tr = self.tracer
        tr.begin_step()
        if self.routed_layers:
            self.routing_counts = []  # the last step's stay with who took them
        if self.reads.selection:
            self.selected_positions = []
        with self._phase("schedule"):
            plan = self.scheduler.schedule()
        if self._acct is not None:
            self._acct["plan"] = plan

        if plan.copies:
            if self.xla is not None:
                # Two staged int32 page-id scalars per CoW copy.
                self.xla.count_h2d(8 * len(plan.copies))
            with self._phase("cow"):
                for _slot, src, dst in plan.copies:
                    # Copy-on-write fans out to every pool: the draft pool
                    # shares page ids with the target pool, so a page that
                    # splits, splits everywhere.
                    self.pools.copy_page(
                        self._copy_page,
                        jnp.asarray(src, jnp.int32),
                        jnp.asarray(dst, jnp.int32),
                    )

        if self.hostkv is not None:
            # Drain spills the schedule phase dispatched (evictions under
            # allocation pressure) into the host buffers, then stage this
            # plan's host-tier fetches. Both run BEFORE the empty-plan
            # early return: a fetch whose request was preempted in the
            # same schedule must still land (its trie entry is live), and
            # fetched pages must be written before any prefill/decode
            # below reads them — the cache data dependency orders that.
            if self.hostkv.pending_spills:
                with self._phase("spill"):
                    spilled = self.hostkv.drain_spills()
                if spilled and self.xla is not None:
                    self.xla.count_d2h(spilled, tag="hostkv_spill")
            if plan.fetches:
                with self._phase("fetch"):
                    self._execute_fetches(plan.fetches)

        if plan.empty:
            # Nothing to dispatch — drain the outstanding readback (e.g.
            # the final token of the last request) before reporting idle.
            if self._inflight is not None:
                with self._phase("readback"):
                    finished = self._resolve_inflight()
            else:
                finished = []
            if tr.enabled:
                self._end_step_trace(plan)
            return finished

        if self.speculative:
            return self._step_spec(plan)

        if plan.prefill:
            chaos.on_serving_phase("mid_prefill")
            with self._phase("prefill"):
                for slot, tokens in plan.prefill:
                    self._prefill_piece(slot, tokens)

        finished: List[int] = []
        dispatched = None
        if plan.decode_slots:
            with self._phase("dispatch"):
                # Partition this step's decode rows. Async rows (no mods,
                # or bias-only — their bias row is request-constant) keep
                # the classic one-dispatch overlap via ``prev``/
                # ``use_prev``. Grammar rows (the next mask depends on
                # this step's token) and each adapter's rows (their group
                # swaps merged params into the SAME compiled program — a
                # jit cache hit, never a recompile) dispatch as separate
                # SYNC groups resolved in-step: the "mods tax" is losing
                # dispatch/readback overlap for those rows only.
                async_slots: List[int] = []
                sync_groups: Dict[Optional[str], List[int]] = {}
                for slot in plan.decode_slots:
                    ms = self.scheduler.slots[slot].mods
                    if ms is not None and ms.needs_sync:
                        sync_groups.setdefault(ms.adapter, []).append(slot)
                    else:
                        async_slots.append(slot)
                if async_slots:
                    prev = (
                        self._inflight[0] if self._inflight is not None
                        else self._zero_prev
                    )
                    nxt = self._dispatch_decode(
                        async_slots, self.params, prev
                    )
                    dispatched = (
                        nxt,
                        async_slots,
                        [
                            self.scheduler.note_decode_dispatched(s)
                            for s in async_slots
                        ],
                    )
                sync_rounds = []
                for adapter, slots in sorted(
                    sync_groups.items(),
                    key=lambda kv: (kv[0] is not None, kv[0] or ""),
                ):
                    group_params = (
                        self.params if adapter is None
                        else self.adapters.params_for(adapter)
                    )
                    nxt = self._dispatch_decode(
                        slots, group_params, self._zero_prev
                    )
                    sync_rounds.append((
                        nxt,
                        slots,
                        [
                            self.scheduler.note_decode_dispatched(s)
                            for s in slots
                        ],
                    ))
                for nxt, slots, reqs in sync_rounds:
                    finished.extend(self._resolve_rows(nxt, slots, reqs))
        if dispatched is not None:
            # The dispatched decode is in flight, its readback not taken:
            # the window a kill_mid_verify drill targets.
            chaos.on_serving_phase("mid_verify")
        # Resolve LAST step's tokens now — the np.asarray sync overlaps
        # with the decode dispatched above.
        if self._inflight is not None:
            with self._phase("readback"):
                finished.extend(self._resolve_inflight())
        self._inflight = dispatched
        if not self.overlap and self._inflight is not None:
            with self._phase("readback"):
                finished.extend(self._resolve_inflight())
        self.metrics.observe_step(new_tokens=len(plan.decode_slots))
        if tr.enabled:
            self._end_step_trace(plan)
        return finished

    def _step_spec(self, plan) -> List[int]:
        """Execute one speculative plan. The draft+verify round is
        dispatched FIRST (device-async), the step's prefill chunks run
        through both models while it computes, and only then does the host
        block on the round's readback — speculative rounds must resolve
        within their own step (the next schedule needs each row's accepted
        count), so overlap here means hiding the sync under prefill rather
        than deferring it a step like the plain path. Keys are staged as
        in :meth:`_dispatch_decode` and folded inside ``_spec_step``."""
        tr = self.tracer
        dispatched = None
        if plan.decode_slots:
            with self._phase("dispatch"):
                self._stage_tables.fill(0)
                self._stage_lens.fill(0)
                for slot in plan.decode_slots:
                    req = self.scheduler.slots[slot]
                    pos = req.len_cached
                    # Synchronous resolution means no PENDING placeholders:
                    # the row's input is always a real token.
                    self._stage_tokens[slot] = req.tokens[pos]
                    self._stage_tables[slot] = req.table.as_row(
                        self.pages_per_seq
                    )
                    self._stage_lens[slot] = pos
                    self._stage_temps[slot] = req.params.temperature
                self._stage_row_keys(plan.decode_slots)
                staged = (
                    self._stage_tokens.nbytes
                    + self._stage_tables.nbytes
                    + self._stage_lens.nbytes
                    + self._stage_temps.nbytes
                    + self._stage_keys.nbytes
                )
                with self._phase(
                    "dispatch.stage",
                    rows=len(plan.decode_slots), bytes=staged,
                ):
                    if self.xla is not None:
                        self.xla.count_h2d(staged)
                    spec_step = self._spec_step
                    tokens = _staged(self._stage_tokens)
                    tables = _staged(self._stage_tables)
                    lens = _staged(self._stage_lens)
                    temps = _staged(self._stage_temps)
                    keys = _staged(self._stage_keys)
                with self._phase("dispatch.launch"):
                    emitted, n_acc, self.cache, self.draft_cache = (
                        spec_step(
                            self.params, self.draft_params,
                            self.cache, self.draft_cache,
                            tokens, tables, lens, temps, keys,
                        )
                    )
                dispatched = (
                    emitted,
                    n_acc,
                    [
                        (s, self.scheduler.slots[s])
                        for s in plan.decode_slots
                    ],
                )
        if dispatched is not None:
            # Draft+verify round in flight, per-row acceptance unknown to
            # the host — the state a kill_mid_verify drill interrupts.
            chaos.on_serving_phase("mid_verify")

        if plan.prefill:
            chaos.on_serving_phase("mid_prefill")
            with self._phase("prefill"):
                for slot, tokens in plan.prefill:
                    self._prefill_piece(slot, tokens)

        finished: List[int] = []
        new_tokens = 0
        if dispatched is not None:
            with self._phase("readback"):
                emitted, n_acc, slot_reqs = dispatched
                with self._phase(
                    "readback.wait", bytes=emitted.nbytes + n_acc.nbytes
                ):
                    # the ONE blocking sync
                    emitted_host = np.asarray(emitted)
                    n_acc_host = np.asarray(n_acc)
                if self.xla is not None:
                    self.xla.count_d2h(
                        emitted_host.nbytes + n_acc_host.nbytes
                    )
                now = time.perf_counter()
                with self._phase(
                    "readback.resolve", rows=len(slot_reqs)
                ) as span:
                    for slot, req in slot_reqs:
                        accepted = int(n_acc_host[slot])
                        n_emit = min(accepted + 1, self.gamma)
                        if self._acct is not None:
                            self._acct["emitted"] += n_emit
                            self._acct["proposed"] += self.gamma
                        toks = [
                            int(t) for t in emitted_host[slot, :n_emit]
                        ]
                        before = req.n_generated
                        done = self.scheduler.resolve_spec(
                            req, toks, now=now
                        )
                        self.metrics.observe_verify(
                            accepted=accepted, emitted=n_emit,
                            gamma=self.gamma,
                        )
                        if tr.enabled:
                            tr.request_event(
                                req.req_id, "verify_round",
                                accepted=accepted, emitted=n_emit,
                                n_generated=req.n_generated,
                            )
                        new_tokens += req.n_generated - before
                        if done is not None:
                            self.scheduler.retire(done, now=now)
                            self.metrics.observe_finished(done)
                            self._keys.pop(done.req_id, None)
                            finished.append(done.req_id)
                    span.note(finished=len(finished))
        self.metrics.observe_step(new_tokens=new_tokens)
        if tr.enabled:
            self._end_step_trace(plan)
        return finished

    def poll(self, req_id: int) -> RequestStatus:
        req = self.requests[req_id]
        return RequestStatus(
            req_id=req_id,
            state=req.state.value,
            prompt_len=len(req.prompt),
            generated=list(req.generated),
            finished=req.done,
            preempt_count=req.preempt_count,
        )

    def cancel(self, req_id: int) -> bool:
        """Client-side cancellation: retire ``req_id`` mid-flight with the
        CANCELLED terminal state and free its pages immediately. Partial
        output stays pollable. Returns False when the request is unknown
        or already terminal."""
        req = self.requests.get(req_id)
        if req is None:
            return False
        return self.scheduler.cancel(req)

    # -------------------------------------------------- observability wire

    def serve(self, host: str = "127.0.0.1", port: int = 0):
        """Start the HTTP introspection server for this engine (see
        ``obs/server.py``): ``/metrics``, ``/healthz``, ``/statusz``,
        ``/snapshot``, ``/trace``, ``/postmortem``. ``port=0`` binds an
        ephemeral port; read it from the returned server's ``.url``.
        Idempotent; stopped automatically by :meth:`close`. While a server
        is attached, :meth:`step` and :meth:`submit` run under the
        registry lock so scrapes observe step boundaries only — device
        work and tokens are untouched."""
        if self._server is None:
            from distributed_pytorch_tpu.obs.server import (
                IntrospectionServer,
            )

            self._server = IntrospectionServer(
                self, host=host, port=port
            ).start()
        return self._server

    def health(self) -> str:
        """``"live"`` / ``"draining"`` / ``"closed"`` — the ``/healthz``
        verdict (only ``"live"`` answers 200)."""
        if self._closed:
            return "closed"
        if self.admission.draining:
            return "draining"
        return "live"

    def trace_documents(self) -> List[dict]:
        """Every Perfetto trace document this component can vouch for —
        for a bare engine, its own tracer's. The ``/requestz`` handler
        merges these (via ``obs.disttrace.merge_traces``) to build
        per-request waterfalls; the front door overrides the same hook to
        add its own and its backend's lanes. Empty when untraced."""
        if not self.tracer.enabled:
            return []
        with self.registry.lock:
            return [self.tracer.to_perfetto()]

    def status(self) -> dict:
        """The ``/statusz`` document: one JSON-serializable dict of engine
        live-state — queue/slot occupancy with per-request phase, age and
        token counts, page-state counts, admission verdicts, SLO firing
        set, goodput split, the XLA program ledger, and recompile-sentinel
        state. Taken under the registry lock, so a server-thread caller
        sees a step-boundary-consistent view."""
        with self.registry.lock:
            now = time.perf_counter()
            out = {
                "health": self.health(),
                "engine": {
                    "speculative": self.speculative,
                    "mesh": self.mesh_fingerprint,
                    "max_slots": self.max_slots,
                    "overlap": self.overlap,
                    "steps": self.metrics.engine_steps,
                    "closed": self._closed,
                },
                "queue_depth": self.scheduler.num_waiting,
                "running_requests": len(self.scheduler.running),
                "inflight_dispatch": self._inflight is not None,
                "requests": self.scheduler.describe_requests(now=now),
                "pages": self.allocator.counters(),
                "admission": self.admission.status(),
                "latency": {
                    "ttft_p50_s": self.registry.read_quantile(
                        "ttft_seconds", 0.5
                    ),
                    "ttft_p95_s": self.registry.read_quantile(
                        "ttft_seconds", 0.95
                    ),
                    "tpot_p50_s": self.registry.read_quantile(
                        "tpot_seconds", 0.5
                    ),
                    "tpot_p95_s": self.registry.read_quantile(
                        "tpot_seconds", 0.95
                    ),
                    "tokens_per_sec": self.metrics.snapshot()[
                        "tokens_per_sec"
                    ],
                },
            }
            if self.state_layers:
                out["state"] = {
                    "layers": self.state_layers,
                    "bytes_per_slot": self.state_bytes_per_slot,
                    "state_slots_in_use": len(self.scheduler.running),
                    "state_bytes": self._state_bytes(),
                    "resets": self.state_resets,
                }
            if self.prefix_cache is not None:
                out["prefix_cache"] = self.prefix_cache.stats()
            if self.hostkv is not None:
                out["hostkv"] = self.hostkv.status()
            if self.slo is not None:
                slo_state = self.slo.state()
                out["slo"] = {
                    "firing": sorted(
                        name
                        for name, st in slo_state.items()
                        if st["firing"]
                    ),
                    "objectives": slo_state,
                }
            if self.goodput is not None:
                out["goodput"] = self.goodput.report()
            if self.xla is not None:
                out["xla"] = self.xla.metadata()
            if self.sentinel is not None:
                out["recompile_sentinel"] = self.sentinel.status()
            if self.timeseries is not None:
                out["timeseries"] = self.timeseries.status()
            if self.regress is not None:
                out["perf_regress"] = self.regress.state()
            if self.roofline is not None:
                out["roofline"] = self.roofline.report()
            return out

    def arm_recompile_sentinel(self) -> RecompileSentinel:
        """Declare warmup over: from here on, every new XLA compilation —
        a ledger signature miss or a backend-compile event no ledgered
        program accounts for (named by JAX's own name of the program) —
        bumps ``serving_engine_recompiles_total``, records a ``recompile``
        flight event with the program name + shapes, and latches the
        firing gauge. Requires ``xla_ledger`` (programs must have been
        wrapped at construction)."""
        if self.sentinel is None:
            raise RuntimeError(
                "recompile sentinel requires the XLA ledger; construct "
                "with InferenceEngine(..., xla_ledger=True)"
            )
        self.sentinel.arm()
        return self.sentinel

    # ------------------------------------------------------- elastic hooks

    def stop_admission(self) -> None:
        """First act of the drain protocol: submit() rejects with
        :class:`~.admission.EngineDraining` from now on. Idempotent."""
        self.admission.close()

    def resume_admission(self) -> None:
        self.admission.reopen()

    def finish_inflight(self) -> List[int]:
        """Resolve the outstanding overlapped decode dispatch, if any (the
        one blocking readback), retiring whatever it finished. After this
        no request holds a PENDING placeholder — the quiescent point the
        snapshot codec and close() both need. Returns finished ids."""
        finished = [] if self._inflight is None else self._resolve_inflight()
        if self.routed_layers and self.tracer.enabled:
            self._flush_routing()
        return finished

    def drain(self):
        """Stop admission, finish the in-flight step, and return an
        :class:`~distributed_pytorch_tpu.serving.elastic.EngineSnapshot`
        of every still-live request — the SIGTERM-with-notice protocol.
        Convenience delegate; see ``serving/elastic.py`` for the pieces."""
        from distributed_pytorch_tpu.serving.elastic import drain_engine

        return drain_engine(self)

    # --------------------------------------------------------- postmortems

    def _dump_postmortem(self, reason: str):
        """Write the flight-recorder ring (plus a goodput report and a
        registry snapshot) as a postmortem document. No-op without a
        recorder; never raises — a failed postmortem must not mask the
        failure being documented."""
        if not self.flight.enabled:
            return None
        try:
            extra = {}
            if self.goodput is not None:
                extra["goodput"] = self.goodput.report()
            extra["registry"] = self.registry.snapshot()
            return self.flight.dump(reason, extra=extra)
        except Exception:
            return None

    def _on_chaos_fault(self, kind: str, step: int, mode: str) -> None:
        """Chaos fault observer — runs BEFORE the fault signal/raise, so
        the dump survives even a SIGKILL drill."""
        self.flight.record(
            "chaos_fault", fault_kind=kind, step=step, mode=mode
        )
        self._dump_postmortem(f"chaos:{kind}")

    def _flush_on_crash(self, reason: str, exc: BaseException) -> None:
        """Last-gasp flush for unhandled exceptions escaping the engine
        loop: record the exception, dump the postmortem, save the trace.
        Every step is best-effort — the original exception re-raises."""
        if self.flight.enabled:
            self.flight.record(
                "exception", reason=reason, error=repr(exc)
            )
        self._dump_postmortem(reason)
        if self.tracer.enabled and self.trace_path:
            try:
                self.tracer.save(self.trace_path)
            except Exception:
                pass

    def close(self) -> None:
        """Deterministic teardown: resolve the in-flight overlapped
        dispatch (no dangling device readback), stop admission, cancel
        every non-terminal request (pages back to the allocator), assert
        via the allocator gauges that zero pages leaked, dump the flight
        recorder, and flush the tracer to ``trace_path`` when one was
        configured. Idempotent; runs automatically on
        ``with InferenceEngine(...) as eng:`` exit."""
        if self._closed:
            return
        with self.registry.lock:
            self.finish_inflight()
            self.stop_admission()
            for req in (
                list(self.scheduler.waiting) + self.scheduler.running
            ):
                self.scheduler.cancel(req)
            self._closed = True
            if self.hostkv is not None:
                # Spills dispatched by the cancellation sweep above (or a
                # final step) must reach the host buffers and the ledger
                # before the leak gates run.
                spilled = self.hostkv.drain_spills()
                if spilled and self.xla is not None:
                    self.xla.count_d2h(spilled, tag="hostkv_spill")
            self.allocator.assert_quiescent()
            if self.window_group is not None:
                self.window_group.allocator.assert_quiescent()
            if self.hostkv is not None:
                self.hostkv.assert_quiescent()
            if self.flight.enabled:
                chaos.remove_fault_observer(self._on_chaos_fault)
                self._dump_postmortem("close")
            if self.tracer.enabled and self.trace_path:
                self.tracer.save(self.trace_path)
        if self.sentinel is not None:
            self.sentinel.disarm()
        if self._server is not None:
            self._server.stop()
            self._server = None

    def __enter__(self) -> "InferenceEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def run(self, max_steps: int = 10_000) -> List[int]:
        """Drive :meth:`step` until the engine drains; returns every
        request id finished along the way. ``max_steps`` bounds a scheduling
        bug to a loud failure instead of a hang. An exception escaping the
        loop flushes the tracer and dumps the flight recorder before
        re-raising — crashes leave a postmortem, not just a traceback."""
        finished: List[int] = []
        steps = 0
        try:
            while self.scheduler.has_work or self._inflight is not None:
                if steps >= max_steps:
                    raise RuntimeError(
                        f"engine did not drain within {max_steps} steps "
                        f"({self.scheduler.num_waiting} waiting, "
                        f"{len(self.scheduler.running)} running)"
                    )
                finished.extend(self.step())
                steps += 1
        except BaseException as exc:
            self._flush_on_crash("exception", exc)
            raise
        return finished

    def stats(self) -> Dict[str, float]:
        """Metrics snapshot + admission counters + cache pressure +
        prefix-cache hit rates."""
        out = self.metrics.snapshot()
        out.update(self.admission.counters())
        out["preemptions"] = self.scheduler.preemptions
        out["expired"] = self.scheduler.expired
        out["cancelled"] = self.scheduler.cancelled
        out["drains"] = self.drains
        out["restores"] = self.restores
        out["requests_recovered"] = self.requests_recovered
        out["cow_copies"] = self.scheduler.cow_copies
        out["prefill_programs"] = self.prefill_programs
        out["prefill_tokens"] = self.prefill_tokens
        out["prefill_width"] = self.prefill_width
        out.update(self.reads.totals)
        # The kinds of layer the model has, and of router: read off the model.
        model = self.decode_model
        out["layer_kinds"] = ",".join(sorted(set(
            getattr(model, "layer_types", None) or ("attention",))))
        if self.routed_layers:
            out["moe_router"] = getattr(model, "routed_router", "linear")
            out["moe_product"] = self.moe_product
            out["moe_pairs_held"] = self.moe_pairs_held
            out["moe_rows_computed"] = self.moe_rows_computed
        out["page_bytes_per_token_layer"] = self.page_bytes_per_token_layer
        for kernel, form in self.reads.forms.items():
            out[f"{kernel}_decode_block_form"] = form
        out["pages_free"] = self.allocator.num_free
        out["pages_allocated"] = self.allocator.num_allocated
        out["pages_idle"] = self.allocator.num_idle
        out["page_evictions"] = self.allocator.evictions
        if self.window_group is not None:
            group = self.window_group
            out["window_pages_freed"] = group.pages_freed
            out["window_pages_held"] = group.allocator.num_allocated
            out["window_pages_held_peak"] = group.pages_held_peak
            out["window_pages_free"] = group.allocator.num_free
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.stats())
        if self.hostkv is not None:
            out.update(self.hostkv.counters())
        if self.goodput is not None:
            gp = self.goodput.report()
            out["goodput_fraction"] = gp["goodput_fraction"]
            out["goodput_productive_s"] = gp["productive_s"]
            out["goodput_wasted_s"] = gp["wasted_total_s"]
            out["goodput_mfu"] = gp["mfu"]
            out["goodput_tokens_per_sec_per_device"] = gp[
                "tokens_per_sec_per_device"
            ]
        return out

    def save_trace(self, path: str) -> str:
        """Write the Perfetto trace to ``path`` (see
        :meth:`~distributed_pytorch_tpu.obs.Tracer.save`). Raises unless
        the engine was constructed with a :class:`Tracer`."""
        if not self.tracer.enabled:
            raise RuntimeError(
                "engine has no tracer; construct with "
                "InferenceEngine(..., tracer=Tracer()) to record"
            )
        return self.tracer.save(path)
