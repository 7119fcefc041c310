"""Measured block-size selection for the Pallas flash-attention kernel.

Replaces the round-1 hardcoded ``(512, 1024)`` guess (VERDICT item 8) with a
tiered lookup, cheapest first:

1. the in-process cache,
2. an explicit precomputed table file (``FLASH_BLOCKS_TABLE=/path.json`` —
   see the pod workflow below),
3. the on-disk cache of this machine's own measured sweeps (``~/.cache/...``),
4. a shipped table measured on real hardware (``DEFAULT_TABLE`` below, keyed
   by device kind), nearest-``T`` entry wins,
5. for device kinds with no measured entry, the VMEM-reasoned
   :func:`analytic_default` (largest legal tile, square-preferred — see its
   docstring; the old bare ``(512, 1024)`` guess remains only as the
   last-resort ``_FALLBACK`` when no candidate is legal).

To add a NEW device kind to the shipped table, run
``tools/flash_autotune_gen.py`` on one host of that kind — it sweeps the
standard shapes and prints a ready-to-paste ``DEFAULT_TABLE`` entry plus a
``FLASH_BLOCKS_TABLE`` JSON for immediate pod deployment.

A full *measured sweep* (``autotune()``) compiles and times each legal
``(block_q, block_k)`` candidate (timed to ``block_until_ready``) and caches
the winner. That costs one kernel compile per candidate, so it never runs
implicitly: call it directly,
run ``python -m distributed_pytorch_tpu.ops.flash_autotune``, or set
``FLASH_AUTOTUNE=1`` to let :func:`flash_attention` sweep on first call per
shape.

**Multi-host pods**: the live sweep is disabled under multi-process SPMD on
purpose (hosts could time different winners and trace divergent programs
around the same collectives — hang). Instead, generate the table OFFLINE on
one host of the same device kind and ship it to every host::

    python -m distributed_pytorch_tpu.ops.flash_autotune \
        --seq_lens 8192,16384 --head_dims 64,128 --export v5e_blocks.json
    # then on every pod host:
    export FLASH_BLOCKS_TABLE=/shared/v5e_blocks.json

The explicit table outranks each host's private disk cache, so all hosts are
guaranteed identical block choices (deterministic traces) even when their
local caches disagree. The shipped DEFAULT_TABLE numbers were measured on
TPU v5e in July 2026 (round 2), under an earlier JAX.

:func:`lookup_with_tier` / :func:`lookup_paged_with_tier` say which tier
answered, for callers that must know their blocks came from the committed
tables (``chip_smoke.py``).
"""

from __future__ import annotations

import functools
import json
import os
import time
from typing import Iterable, Optional, Tuple

# (t_bucket, head_dim) -> (block_q, block_k); nearest t_bucket is used.
# Measured on TPU v5 lite (v5e), causal fwd+bwd, bf16 (round 2, July
# 2026). At T=2048 all candidates sit within dispatch noise;
# from T=8192 up, (1024, 1024) beats the round-1 (512, 1024) guess by
# ~6-10%, and (1024, 2048) exceeds VMEM (the sweep skips failures).
DEFAULT_TABLE = {
    "tpu v5 lite": {
        (2048, 64): (256, 512),
        (2048, 128): (1024, 1024),
        (8192, 64): (1024, 1024),
        (8192, 128): (512, 2048),
        (16384, 64): (1024, 1024),
        (16384, 128): (1024, 1024),
    },
}

#: Cache-key family tag for the paged flash-decode kernel
#: (ops/paged_attention.py). Paged entries share the tiered lookup,
#: disk-cache file, and FLASH_BLOCKS_TABLE schema with the training
#: kernel's — the family tag rides inside the key's dtype slot
#: (``"paged_decode:<dtype>:p<page_size>"``), so the two families can
#: never collide and existing table files keep decoding unchanged.
PAGED_FAMILY = "paged_decode"

# device_kind -> pages-per-block for the paged decode kernel. The "cpu"
# entry is the SEEDED interpret/CI value: the test rig resolves its block
# size from here, so CI never runs a sweep. The "tpu v5 lite" entry is the
# winner of a sweep on the chip (PR 29; the table is in PERF.md section 6)
# at both shapes the benchmark serves, bf16 pages of 16 tokens and head size
# 128: 32 slots of 256 pages with 24 query heads on 2 KV heads, at a full
# batch of ~400 tokens a row and at 4 live rows of ~1,500; 128 slots of 128
# pages with 20 heads on 1, at ~290 tokens a row. 8 is within 4-8% of it
# there; rows of thousands of tokens would take 32 or 64. Since PR 48 a block
# is computed on its tiles as stored, bf16 into both products (the kernel's
# docstring); swept again on the chip at 8, 16 and 32 pages and six
# geometries (PERF.md section 6, PR 48): 16 is within 6% of the best at 24 on
# 2 (32 rows; 32 pages win by 15% at 4 long rows), at 20 on 1 and at 64 on 8;
# at 32 on 32 KV heads 8 pages win by 12% (ROADMAP S3 (d)). The number stays.
PAGED_DEFAULT_TABLE = {
    "cpu": 2,
    "tpu v5 lite": 16,
    # A (device kind, head size) entry goes before its kind's: the latent
    # kernel's pool rows of 640 (``models/mla.py``: 16 query heads on ONE
    # cached vector a token, bf16 pages of 16 tokens, 32 slots of 1,024 pages
    # at 4,400-13,200 tokens a row). With no row sharing a page (my chip run,
    # PR 35, that PR's kernel): 16 pages a block take 941 us a call, 32 703,
    # 64 622, **128 596**, 256 707. With tables that share as a prefix
    # cache's do (``--sharers 2``: 16 documents, two rows each, tails of
    # 100-500 tokens; my chip run, PR 36, the kernel that copies a shared
    # document once): 16 pages 702 us, 32 498, 64 425, **128 372**, 256 390;
    # a walk's last block is copied at an eighth of the block or more, so a
    # large block no longer taxes a short tail.
    ("tpu v5 lite", 640): 128,
}

_FALLBACK = (512, 1024)
_PAGED_FALLBACK = 4  # pages per block when nothing is known about the chip
_runtime_cache: dict = {}
# Keys whose measured sweep failed outright (no candidate compiled) in THIS
# process: memoized so the live FLASH_AUTOTUNE=1 path doesn't re-pay the
# failing sweep per retrace, distinguishable so the table generator never
# emits the fallback as a measured winner, and never written to disk so a
# future process (new compiler, new driver) retries for real.
_failed_sweeps: set = set()


def _cache_path() -> str:
    root = os.environ.get(
        "XDG_CACHE_HOME", os.path.join(os.path.expanduser("~"), ".cache")
    )
    return os.path.join(root, "distributed_pytorch_tpu", "flash_blocks.json")


def _load_disk_cache() -> dict:
    try:
        with open(_cache_path()) as f:
            return {tuple(json.loads(k)): tuple(v) for k, v in json.load(f).items()}
    except Exception:
        return {}


def _save_disk_cache(cache: dict) -> None:
    path = _cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({json.dumps(list(k)): list(v) for k, v in cache.items()}, f)
    except OSError:
        pass  # read-only home: in-process cache still works


def _key(device_kind: str, t: int, d: int, dtype_name: str, causal: bool):
    return (device_kind.lower(), t, d, dtype_name, bool(causal))


@functools.lru_cache(maxsize=8)
def _load_table_file(path: str) -> dict:
    """Explicit precomputed table (FLASH_BLOCKS_TABLE): same JSON schema as
    the disk cache. Errors are loud — a pod pointing at a bad table should
    fail at startup, not silently fall back to divergent local caches."""
    with open(path) as f:
        return {tuple(json.loads(k)): tuple(v) for k, v in json.load(f).items()}


def candidates(t: int, d: int) -> Iterable[Tuple[int, int]]:
    """Legal (block_q, block_k) pairs for sequence length ``t``: both divide
    ``t``, block_k lane-aligned (multiple of 128), VMEM-bounded."""
    qs = [b for b in (256, 512, 1024) if t % b == 0]
    ks = [b for b in (256, 512, 1024, 2048) if t % b == 0 and b % 128 == 0]
    for bq in qs or [t]:
        for bk in ks or []:
            # Rough VMEM bound: score tile + K/V tiles in fp32.
            if bq * bk * 4 + 2 * bk * d * 4 <= 12 * 2**20:
                yield bq, bk


def analytic_default(t: int, d: int) -> Tuple[int, int]:
    """VMEM-reasoned block choice for device kinds with no measured entry.

    Every TPU generation since v4 carries >=16 MB of VMEM per core, so the
    same 12 MB working budget as :func:`candidates` is legal everywhere the
    kernel runs. Among legal candidates, pick the largest tile area (fewer
    grid steps, longer MXU contractions per program — the direction every
    measured v5e sweep moved in from T=8192 up), breaking ties toward
    square blocks ((1024, 1024) won those sweeps over (512, 2048) at equal
    area). This replaces the old bare ``(512, 1024)`` guess, which pinned
    pods on unmeasured chips to a tiling the v5e sweep beat by 6-10%.
    """
    # Stricter than candidates(): the sweep probes compile-failures at
    # runtime, but this path must never hand out a tiling that cannot
    # compile. The backward kernel holds ~3 score-shaped [bq, bk] f32
    # buffers (S, P, dS), so the measured legality boundary on v5e sits at
    # area 2^20 — (1024, 2048) fails to lower while every area<=2^20
    # candidate compiles (round-2 sweep, July 2026).
    legal = [c for c in candidates(t, d) if c[0] * c[1] <= 1 << 20]
    if not legal:
        return _FALLBACK
    return max(legal, key=lambda c: (c[0] * c[1], min(c)))


def _from_files(key):
    """``(blocks, tier)`` from the two tiers that live outside the
    checkout — the explicit ``FLASH_BLOCKS_TABLE`` file, then this
    machine's disk cache — or ``(None, None)``."""
    table_path = os.environ.get("FLASH_BLOCKS_TABLE")
    if table_path:
        shipped = _load_table_file(table_path)
        if key in shipped:
            return shipped[key], "table_file"
    disk = _load_disk_cache()
    if key in disk:
        return disk[key], "disk_cache"
    return None, None


def lookup_with_tier(
    t: int,
    d: int,
    dtype_name: str = "bfloat16",
    causal: bool = True,
    device_kind: Optional[str] = None,
) -> Tuple[Tuple[int, int], str]:
    """:func:`lookup` past the in-process cache, with the tier that
    answered: ``"table_file"``, ``"disk_cache"``, ``"shipped_table"`` (the
    committed :data:`DEFAULT_TABLE`) or ``"analytic"``."""
    if device_kind is None:
        device_kind = _device_kind()
    blocks, tier = _from_files(_key(device_kind, t, d, dtype_name, causal))
    if blocks is not None:
        return blocks, tier
    table = DEFAULT_TABLE.get(device_kind.lower())
    if table:
        near = min(table, key=lambda k: (abs(k[0] - t), abs(k[1] - d)))
        return table[near], "shipped_table"
    # Unknown chip: reason from VMEM legality instead of guessing.
    return analytic_default(t, d), "analytic"


def lookup(
    t: int,
    d: int,
    dtype_name: str = "bfloat16",
    causal: bool = True,
    device_kind: Optional[str] = None,
) -> Tuple[int, int]:
    """Best-known (block_q, block_k) for this shape without measuring."""
    if device_kind is None:
        device_kind = _device_kind()
    key = _key(device_kind, t, d, dtype_name, causal)
    if key not in _runtime_cache:
        # Memoize table/fallback hits too: repeat lookups (one per trace)
        # must not re-open the disk cache file.
        _runtime_cache[key], _ = lookup_with_tier(
            t, d, dtype_name, causal, device_kind
        )
    return _runtime_cache[key]


def _paged_key(
    device_kind: str, kv_len: int, page_size: int, head_dim: int,
    dtype_name: str,
):
    """Key for the ``paged_decode`` family: same 5-tuple shape as
    :func:`_key` (so every cache tier and table file works unchanged) with
    the family tag and page size folded into the dtype slot."""
    return _key(
        device_kind, int(kv_len), int(head_dim),
        f"{PAGED_FAMILY}:{dtype_name}:p{int(page_size)}", False,
    )


def paged_candidates(pages_per_seq: int, page_size: int):
    """Legal pages-per-block choices for the paged decode kernel: powers of
    two up to the table width, KV-block span bounded so the per-step page
    tiles (2 pools x fp32 worst case) stay comfortably inside VMEM."""
    out, c = [], 1
    while c <= max(1, int(pages_per_seq)):
        if c * page_size <= 4096:
            out.append(c)
        c *= 2
    return out or [1]


def lookup_paged_with_tier(
    kv_len: int,
    page_size: int,
    head_dim: int,
    dtype_name: str = "float32",
    device_kind: Optional[str] = None,
) -> Tuple[int, str]:
    """:func:`lookup_paged` past the in-process cache, with the tier that
    answered (as :func:`lookup_with_tier`; ``"fallback"`` when the device
    kind has no :data:`PAGED_DEFAULT_TABLE` entry)."""
    if device_kind is None:
        device_kind = _device_kind()
    entry, tier = _from_files(
        _paged_key(device_kind, kv_len, page_size, head_dim, dtype_name)
    )
    if entry is not None:
        return int(entry[0]), tier
    kind = device_kind.lower()
    tier = "shipped_table" if kind in PAGED_DEFAULT_TABLE else "fallback"
    npb = PAGED_DEFAULT_TABLE.get(
        (kind, int(head_dim)), PAGED_DEFAULT_TABLE.get(kind, _PAGED_FALLBACK)
    )
    pages_per_seq = max(1, int(kv_len) // max(1, int(page_size)))
    legal = paged_candidates(pages_per_seq, page_size)
    fitting = [c for c in legal if c <= npb]
    return (max(fitting) if fitting else legal[0]), tier


def lookup_paged(
    kv_len: int,
    page_size: int,
    head_dim: int,
    dtype_name: str = "float32",
    device_kind: Optional[str] = None,
) -> int:
    """Best-known pages-per-block for a paged decode shape, tiered exactly
    like :func:`lookup`: runtime cache -> FLASH_BLOCKS_TABLE ->
    disk cache -> seeded :data:`PAGED_DEFAULT_TABLE` -> fallback. Entries
    store ``(pages_per_block, pages_per_block * page_size)`` to keep the
    two-int JSON schema shared with the flash family."""
    if device_kind is None:
        device_kind = _device_kind()
    key = _paged_key(device_kind, kv_len, page_size, head_dim, dtype_name)
    if key not in _runtime_cache:
        npb, _ = lookup_paged_with_tier(
            kv_len, page_size, head_dim, dtype_name, device_kind
        )
        _runtime_cache[key] = (npb, npb * int(page_size))
    return int(_runtime_cache[key][0])


def autotune_paged(
    kv_len: int,
    page_size: int,
    head_dim: int,
    *,
    slots: int = 8,
    kv_heads: int = 8,
    group: int = 1,
    dtype=None,
    context: Optional[int] = None,
    steps: int = 20,
    verbose: bool = False,
    force: bool = False,
    interpret: Optional[bool] = None,
    latent: int = 0,
    sharers: int = 1,
    blocks=None,
    timings: Optional[dict] = None,
) -> int:
    """Measured sweep for the paged decode kernel: times every legal
    pages-per-block over a synthetic decode batch whose rows hold about
    ``context`` tokens each, half to one and a half times it (default: the
    table's whole width; the kernel walks only what a row holds, so sweep
    at the contexts the deployment sees) and caches the winner under the ``paged_decode`` family key
    (in-process + on disk, same persistence rules as :func:`autotune`).
    ``steps`` calls run inside ONE program, each fed the last one's output:
    a call takes tens of microseconds, less than a dispatch. Offline tool —
    the serving path only ever reads :func:`lookup_paged`.

    ``latent > 0`` sweeps the latent kernel instead (``models/mla.py``'s
    page: ONE pool ``[num_pages, page, head_dim]`` with no head axis,
    ``kv_heads * group`` query heads, a value of the row's first ``latent``
    numbers); the winner is cached under the pool's width as its
    ``head_dim``, which is where ``block_pages`` looks it up.

    ``sharers > 1`` builds tables that share as a prefix cache's do
    (:func:`shared_tables`): every ``sharers`` rows hold one document of
    about ``context`` tokens under the same physical pages, and each a tail
    of its own of 100-500 tokens. The latent kernel copies a shared document
    once for its rows, so its block is swept on what it runs.

    ``timings``, a dict, is filled ``{pages_per_block: seconds a call}``: the
    whole table, for whoever wants more than the winner. ``blocks`` times
    just these pages-per-block (any the table's width holds: a window
    group's 9), a measurement that tunes nothing: its winner is not kept."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.ops.paged_attention import (
        paged_attention,
        paged_latent_attention,
    )
    from distributed_pytorch_tpu.utils.platform import on_tpu

    dtype = dtype or jnp.float32
    dtype_name = jnp.dtype(dtype).name
    device_kind = _device_kind()
    key = _paged_key(device_kind, kv_len, page_size, head_dim, dtype_name)
    if not force:
        if key in _runtime_cache:
            return int(_runtime_cache[key][0])
        disk = _load_disk_cache()
        if key in disk:
            _runtime_cache[key] = disk[key]
            return int(disk[key][0])
    if interpret is None:
        interpret = not on_tpu()
    mode = "interpret" if interpret else "pallas"

    pages_per_seq = max(1, kv_len // page_size)
    num_pages = slots * pages_per_seq + 1  # + the reserved null page
    rng = np.random.default_rng(0)
    h = kv_heads * group
    q = jnp.asarray(
        rng.standard_normal((slots, 1, h, head_dim)), dtype
    )
    if latent:
        pools = (jnp.asarray(
            rng.standard_normal((num_pages, page_size, head_dim)), dtype),)
    else:
        pool = (num_pages, page_size, kv_heads, head_dim)
        pools = (jnp.asarray(rng.standard_normal(pool), dtype),
                 jnp.asarray(rng.standard_normal(pool), dtype))
    if sharers > 1:
        tables, held = shared_tables(
            rng, slots, pages_per_seq, page_size, context or kv_len // 2,
            sharers,
        )
    else:
        tables = 1 + np.arange(slots * pages_per_seq).reshape(
            slots, pages_per_seq
        )
        if context is None:
            held = np.full((slots,), kv_len)
        else:  # ragged, as a batch is: half to one and a half times it
            held = rng.integers(
                max(1, context // 2), min(kv_len, context * 3 // 2) + 1,
                slots,
            )
    tables = jnp.asarray(tables, jnp.int32)
    lens = jnp.asarray(held - 1, jnp.int32)

    best, best_dt = None, float("inf")
    for npb in blocks or paged_candidates(pages_per_seq, page_size):
        try:
            if latent:
                # The value is narrower than the query: pad it back.
                def attend(x, *rest, npb=npb):
                    out = paged_latent_attention(
                        x, *rest, v_width=latent, kernel=mode,
                        pages_per_block=npb,
                    )
                    return jnp.pad(
                        out, ((0, 0),) * 3 + ((0, head_dim - latent),)
                    )
            else:
                attend = functools.partial(
                    paged_attention, kernel=mode, pages_per_block=npb
                )
            fn = jax.jit(
                lambda q, *rest: jax.lax.fori_loop(
                    0, steps, lambda _, x: attend(x, *rest), q
                )
            )
            fn(q, *pools, tables, lens).block_until_ready()
            t0 = time.perf_counter()
            fn(q, *pools, tables, lens).block_until_ready()
            dt = (time.perf_counter() - t0) / steps
        except Exception as e:  # lowering failure for this blocking: skip
            if verbose:
                print(f"  npb={npb:3d}: failed ({type(e).__name__})")
            continue
        if verbose:
            print(f"  npb={npb:3d}: {dt * 1e6:9.1f} us")
        if timings is not None:
            timings[npb] = dt
        if dt < best_dt:
            best, best_dt = npb, dt
    if best is None:
        import warnings

        warnings.warn(
            f"paged autotune: no pages-per-block candidate ran for "
            f"kv_len={kv_len} page={page_size} d={head_dim} on "
            f"{device_kind!r}; using fallback {_PAGED_FALLBACK} "
            "(not persisted)"
        )
        _runtime_cache[key] = (_PAGED_FALLBACK, _PAGED_FALLBACK * page_size)
        _failed_sweeps.add(key)
        return _PAGED_FALLBACK
    if blocks:
        return best
    _runtime_cache[key] = (best, best * page_size)
    _failed_sweeps.discard(key)
    disk = _load_disk_cache()
    disk[key] = (best, best * page_size)
    _save_disk_cache(disk)
    return best


def shared_tables(
    rng, slots: int, pages_per_seq: int, page_size: int, context: int,
    sharers: int,
):
    """``(tables [slots, pages_per_seq], held [slots])`` of a decode batch
    whose rows share documents as a prefix cache hands them out: ``slots /
    sharers`` documents of half to one and a half times ``context`` tokens,
    row ``r`` asking of document ``r % documents`` (every document is
    visited before any repeats), under the document's physical pages as far
    as they are whole, then pages of its own for the document's partial page
    (copied on write) and a tail of 100-500 tokens (an eighth of the table
    at most)."""
    import numpy as np

    documents = max(1, slots // sharers)
    capacity = pages_per_seq * page_size
    tail = min(500, capacity // 8)  # a toy table has toy tails
    doc_tokens = rng.integers(
        max(page_size, context // 2),
        min(capacity - tail, context * 3 // 2) + 1, documents,
    )
    tails = rng.integers(max(1, tail // 5), tail + 1, slots)
    tables = np.zeros((slots, pages_per_seq), np.int64)
    held = np.zeros((slots,), np.int64)
    free = 1  # page 0 is the reserved null page
    whole = []
    for tokens in doc_tokens:
        whole.append(np.arange(free, free + tokens // page_size))
        free += len(whole[-1])
    for r in range(slots):
        pages = whole[r % documents]
        held[r] = doc_tokens[r % documents] + tails[r]
        own = -(-held[r] // page_size) - len(pages)
        tables[r, : len(pages)] = pages
        tables[r, len(pages) : len(pages) + own] = np.arange(free, free + own)
        free += own
    return tables, held


def _device_kind() -> str:
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


def autotune(
    t: int,
    d: int,
    *,
    bh: int = 16,
    dtype=None,
    causal: bool = True,
    steps: int = 5,
    verbose: bool = False,
    force: bool = False,
) -> Tuple[int, int]:
    """Measured sweep: times causal fwd+bwd for every legal candidate and
    caches the winner (in-process + on disk). Returns (block_q, block_k).

    ``force=True`` skips the cache READS (still writes) — the table
    generator uses it so a re-run after a compiler upgrade (or with a
    different ``bh``, which the cache key deliberately omits) re-measures
    instead of replaying stale winners. A sweep in which EVERY candidate
    fails to compile returns the legacy fallback, memoized in-process only
    (``_failed_sweeps`` marks it un-measured; the disk cache is never
    written): the live path doesn't re-pay the failing sweep, the table
    generator excludes the shape, and a future process retries for real.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.ops.flash_attention import _flash

    dtype = dtype or jnp.bfloat16
    dtype_name = jnp.dtype(dtype).name
    device_kind = _device_kind()
    key = _key(device_kind, t, d, dtype_name, causal)
    if not force:
        if key in _runtime_cache:
            return _runtime_cache[key]
        disk = _load_disk_cache()
        if key in disk:  # a previous process already swept this shape
            _runtime_cache[key] = disk[key]
            return disk[key]

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((bh, t, d)), dtype) for _ in range(3)
    )

    best, best_dt = _FALLBACK, float("inf")
    for bq, bk in candidates(t, d):
        try:
            loss = jax.jit(
                jax.grad(
                    lambda q, k, v: jnp.sum(
                        _flash(q, k, v, causal, bq, bk, False).astype(jnp.float32)
                        ** 2
                    )
                )
            )
            jax.block_until_ready(loss(q, k, v))
            t0 = time.perf_counter()
            for _ in range(steps):
                g = loss(q, k, v)
            jax.block_until_ready(g)
            dt = (time.perf_counter() - t0) / steps
        except Exception as e:  # lowering failure for this tiling: skip
            if verbose:
                print(f"  ({bq:5d},{bk:5d}): failed ({type(e).__name__})")
            continue
        if verbose:
            print(f"  ({bq:5d},{bk:5d}): {dt * 1e3:8.2f} ms")
        if dt < best_dt:
            best, best_dt = (bq, bk), dt
    if best_dt == float("inf"):
        # Nothing compiled. Memoize in-process only (the live path must not
        # re-pay a failing sweep per retrace; a future process with a newer
        # compiler should retry), mark the key failed so the table
        # generator excludes it, and return the fallback.
        import warnings

        warnings.warn(
            f"flash autotune: no (block_q, block_k) candidate compiled for "
            f"T={t} d={d} on {device_kind!r}; using fallback {_FALLBACK} "
            "(not persisted)"
        )
        _runtime_cache[key] = _FALLBACK
        _failed_sweeps.add(key)
        return _FALLBACK
    _runtime_cache[key] = best
    _failed_sweeps.discard(key)
    disk = _load_disk_cache()
    disk[key] = best
    _save_disk_cache(disk)
    return best


@functools.lru_cache(maxsize=None)
def autotune_enabled() -> bool:
    return os.environ.get("FLASH_AUTOTUNE", "") not in ("", "0")


def main(argv=None) -> None:
    """Sweep representative shapes on the current device; print a
    ready-to-paste ``DEFAULT_TABLE`` entry and optionally export a
    ``FLASH_BLOCKS_TABLE`` JSON. ``tools/flash_autotune_gen.py`` is the
    documented alias of this entry point — one implementation, two names.

    Only MEASURED winners are emitted: ``--force`` re-sweeps past any
    cached entry, and a shape where every candidate failed to compile is
    reported and EXCLUDED from both outputs (measured-ness is checked
    against the disk cache, which a failed sweep never writes)."""
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--seq_lens", default="2048,8192,16384")
    parser.add_argument("--head_dims", default="64,128")
    parser.add_argument("--bh", default=16, type=int, help="batch*heads")
    parser.add_argument(
        "--export", default="",
        help="write the swept entries to this JSON (ship to pod hosts via "
        "FLASH_BLOCKS_TABLE so every host picks identical blocks)",
    )
    parser.add_argument(
        "--force", action="store_true",
        help="re-measure even when a cached winner exists (use after a "
        "compiler/runtime upgrade or with a different --bh)",
    )
    parser.add_argument(
        "--paged", action="store_true",
        help="sweep the paged_decode family (pages-per-block for the "
        "serving decode kernel) instead of the training flash family",
    )
    parser.add_argument(
        "--kv_lens", default="512,2048,8192",
        help="paged sweep: per-sequence KV capacities (block-table width "
        "x page size)",
    )
    parser.add_argument(
        "--page_sizes", default="16,64",
        help="paged sweep: KV page sizes to tune for",
    )
    parser.add_argument("--slots", default=8, type=int,
                        help="paged sweep: decode batch size")
    parser.add_argument("--kv_heads", default=8, type=int,
                        help="paged sweep: KV heads")
    parser.add_argument("--group", default=1, type=int,
                        help="paged sweep: query heads a KV head")
    parser.add_argument(
        "--context", default=None, type=int,
        help="paged sweep: tokens a row holds, about (default: the whole table)",
    )
    parser.add_argument("--dtype", default="float32",
                        help="paged sweep: dtype of queries and pages")
    parser.add_argument(
        "--latent", default=0, type=int,
        help="paged sweep: the latent kernel (one pool of --head_dims wide "
        "rows, no head axis) with a value of the row's first LATENT numbers",
    )
    parser.add_argument(
        "--sharers", default=1, type=int,
        help="paged sweep: rows that hold each document of --context tokens "
        "under the same physical pages, with a tail of 100-500 tokens each "
        "(1: no row shares a page)",
    )
    parser.add_argument(
        "--blocks", default="",
        help="paged sweep: the pages-per-block to time (default: every legal "
        "one)",
    )
    parser.add_argument("--steps", default=20, type=int,
                        help="paged sweep: calls timed inside one program")
    parser.add_argument(
        "--out", default="",
        help="paged sweep: append each shape's timings to this JSON-lines "
        "file",
    )
    args = parser.parse_args(argv)
    kind = _device_kind()
    if kind == "unknown":
        raise SystemExit("no JAX backend reachable — run on the target device")
    print(f"device: {kind}", flush=True)
    entries = {}  # (t, d) -> measured blocks
    shipped = {}  # full key -> blocks, for --export
    failed = []

    if args.paged:
        paged_entries = []  # (kv_len, page, d) -> npb
        for kv_len in (int(x) for x in args.kv_lens.split(",")):
            for page in (int(x) for x in args.page_sizes.split(",")):
                for d in (int(x) for x in args.head_dims.split(",")):
                    print(f"kv={kv_len} page={page} d={d}:", flush=True)
                    timings = {}
                    npb = autotune_paged(
                        kv_len, page, d, slots=args.slots,
                        kv_heads=args.kv_heads, group=args.group,
                        dtype=args.dtype, context=args.context,
                        verbose=True, force=args.force, latent=args.latent,
                        sharers=args.sharers, steps=args.steps,
                        blocks=[int(x) for x in args.blocks.split(",") if x],
                        timings=timings,
                    )
                    if args.out:
                        with open(args.out, "a") as f:
                            f.write(json.dumps({
                                "device": kind, "kv_len": kv_len, "page": page,
                                "head_dim": d, "slots": args.slots,
                                "kv_heads": args.kv_heads, "group": args.group,
                                "context": args.context, "dtype": args.dtype,
                                "steps": args.steps,
                                "us_a_call": {
                                    b: dt * 1e6 for b, dt in timings.items()
                                },
                            }) + "\n")
                    key = _paged_key(kind, kv_len, page, d, args.dtype)
                    if key in _failed_sweeps:
                        print("  -> MEASUREMENT FAILED (excluded)", flush=True)
                        failed.append((kv_len, page, d))
                        continue
                    print(f"  -> pages_per_block={npb}", flush=True)
                    paged_entries.append(npb)
                    shipped[key] = (npb, npb * page)
        if paged_entries:
            # The seeded table is one npb per device kind; suggest the
            # most common measured winner across shapes.
            best = max(set(paged_entries), key=paged_entries.count)
            print("\n# Paste into ops/flash_autotune.py PAGED_DEFAULT_TABLE:")
            print(f'    "{kind.lower()}": {best},', flush=True)
        if failed:
            print(f"\n# NOT measured: {failed}", flush=True)
        if args.export:
            with open(args.export, "w") as f:
                json.dump(
                    {json.dumps(list(k)): list(v) for k, v in shipped.items()},
                    f,
                )
            print(
                f"exported {len(shipped)} measured entries to {args.export} — "
                "deploy with FLASH_BLOCKS_TABLE=<path> on every pod host",
                flush=True,
            )
        return

    for t in (int(x) for x in args.seq_lens.split(",")):
        for d in (int(x) for x in args.head_dims.split(",")):
            blocks = autotune(t, d, bh=args.bh, verbose=True, force=args.force)
            key = _key(kind, t, d, "bfloat16", True)
            # Measured-ness comes from the sweep itself (_failed_sweeps),
            # not the disk cache — stale disk entries or a read-only home
            # must not flip a shape between measured and failed.
            if key in _failed_sweeps:
                print(
                    f"T={t:6d} d={d:4d} -> MEASUREMENT FAILED (excluded)",
                    flush=True,
                )
                failed.append((t, d))
                continue
            analytic = analytic_default(t, d)
            marker = "  (= analytic default)" if blocks == analytic else ""
            print(f"T={t:6d} d={d:4d} -> {blocks}{marker}", flush=True)
            entries[(t, d)] = blocks
            shipped[key] = blocks

    if entries:
        print("\n# Paste into ops/flash_autotune.py DEFAULT_TABLE:")
        print(f'    "{kind.lower()}": {{')
        for (t, d), (bq, bk) in sorted(entries.items()):
            print(f"        ({t}, {d}): ({bq}, {bk}),")
        print("    },", flush=True)
    if failed:
        print(
            f"\n# NOT measured (every candidate failed to compile): {failed}",
            flush=True,
        )
    if args.export:
        with open(args.export, "w") as f:
            json.dump(
                {json.dumps(list(k)): list(v) for k, v in shipped.items()}, f
            )
        print(
            f"exported {len(shipped)} measured entries to {args.export} — "
            "deploy with FLASH_BLOCKS_TABLE=<path> on every pod host",
            flush=True,
        )


if __name__ == "__main__":
    main()
