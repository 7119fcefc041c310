"""What a decode dispatch reads, and what its program has to be told.

ONE owner, :class:`DecodeReads`, built once by the engine from its decode model
and its cache tree (shapes only: no trace, no compile). It hides a decision:
which kernels the model's layers call at decode, at which block, under which
copy rule. The engine hands it a dispatch's staged tables and positions and
asks nothing about kinds of layer; a PR that changes what a kernel copies
changes ``ops/paged_attention.py`` and, where the counting rule's arguments
change, this file.

**The plan** (:attr:`DecodeReads.layers`, :attr:`~DecodeReads.blocks`): how
many layers read by each path, from the model's ``layer_types``: an attention
layer through the K/V kernel (``"kv"``), a ``"latent"`` layer through the
latent kernel, which serves rows whose tables begin with the same pages as a
GROUP (``shared_prefix_groups``: the shared pages copied once), a
``"latent_sparse"`` layer through the index kernel (every visible token's
index key scored, a group's shared blocks once) and a gather of the
``index_top_k`` best tokens' latents, a ``"latent_window"`` layer through the
windowed latent call (the pages that meet the window, every row by itself), a
``"gated_delta"`` layer through its matrix state, in place, a ``"cca"`` layer
through the K/V kernel on the sequence's table AND its slot state. Each kernel's block
is what its call looks up, by the call's own helper on the layer's own pool
(``kv_block_pages`` / ``latent_block_pages`` / ``index_block_pages``); a K/V
kernel's call also names how it computes a block (``pa.KV_BLOCK_FORM``:
:attr:`DecodeReads.forms`, ``stats()``'s ``kv_decode_block_form`` /
``kv_window_decode_block_form``); with the
kernels off (``paged_kernel`` unset or resolved to ``"xla"``) there are no
blocks: the gather path reads every slot's whole table, groups nobody and
starts no page copy.

**The counts** (:meth:`DecodeReads.count`, a dispatch's live rows at positions
``pos``; the host's NumPy on its staged copies, by the kernels' own rules):

* ``decode_kv_tokens_visible`` (``pos + 1`` a row),
  ``decode_kv_tokens_fetched`` (the K/V kernel's whole blocks,
  ``kv_tokens_walked``; the latent kernel's walks, a group's shared pages
  once, ``latent_tokens_fetched``; with sparse or window layers the latent
  rows a layer reads on average: selected, and a window's pages; the whole
  tables on the gather path) and
  ``decode_kv_tokens_distinct`` (the visible tokens with each PHYSICAL page
  once, so rows that share a document count it once): a traced step's only;
* latent layers: ``decode_rows_grouped`` (rows served in a group of two or
  more: by the index kernel, whose group shares a whole block of its own, in
  a model with sparse layers, else by the latent kernel),
  ``decode_page_copies`` (the copy descriptors ONE call of each kernel that
  copies pages by runs starts: ``latent_copies_started`` /
  ``index_copies_started``, the windowed call's on ``window_tables``) and
  ``decode_pages_in_runs`` (the pages among them that went as part of a run of
  neighbouring pages, ``is_run``): the pages copied are the copies less the
  runs plus the pages in runs;
* sparse layers: ``decode_index_tokens_scored`` (``pos + 1``),
  ``decode_index_tokens_fetched`` (``index_tokens_fetched``),
  ``decode_kv_tokens_selected`` (``min(pos + 1, index_top_k)``) and, traced,
  ``decode_index_tokens_scored_distinct``; a ``dsa.select`` instant a dispatch;
* window layers: ``decode_window_tokens_visible`` (``min(pos + 1, window)``)
  and ``decode_window_tokens_read`` (the whole pages that hold them). A K/V
  layer with a window (``"attention_window"``: a block-table group of its
  own, ``serving/kv_cache.py`` ``WindowGroup``) writes the same two, its
  ``read`` being what the K/V kernel copies over the group's short table:
  whole blocks (``kv_block_pages(..., short=True)``: a row's 9 pages are one),
  a page past the row's last copied again in its place; on the gather path
  the short table whole. The full layers' ``decode_kv_tokens_*`` count the
  FULL group's table, as in a model with no window;
* gated-delta and CCA layers: ``state_slots_updated`` (rows x such layers) and
  ``state_bytes_moved`` (each state once in and once out: the matrix state by
  ``linear_attention.state_bytes_moved``, a CCA layer's slot leaves as the
  cache tree holds them), on every ``step`` slice; a ``prefill.chunk`` slice
  carries ``state_blocks``, the blocks a layer evaluated for the piece (of 64
  tokens under the delta rule; a CCA layer's convolutions take a piece whole);
* K/V layers of the full group, a prefill piece (:meth:`DecodeReads.prefill_args`):
  a ``prefill.chunk`` slice carries ``keys_walked`` (the whole blocks the
  chunk walk gathers and scores for a piece that ends at ``start + tokens``,
  by the walk's own rule ``chunk_keys_walked``) and ``keys_table`` (the
  table's width in tokens: what the dense read scored); the ``step`` slice and
  the totals their sums, ``prefill_keys_walked`` / ``prefill_keys_table``.

:attr:`DecodeReads.totals` is what ``stats()`` shows of them; the ``step``
slice carries the step's own (:meth:`DecodeReads.end_step`).

**The operands** (:meth:`DecodeReads.operands`): the decode program applies the
grouping rule once to its table operand and tells its layers (``row_groups``;
beside it ``latent_runs``, which turns of the latent kernel's copy loop are
runs); the host applies the same rule to its own copies for the counts.
Nothing switches grouping on or off: tables that share nothing group nobody.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_pytorch_tpu.models.mamba import STATE_DTYPE, STATE_KEYS
from distributed_pytorch_tpu.models.transformer import (
    FULL_KV_TYPES,
    LATENT_TYPES,
)
from distributed_pytorch_tpu.ops import linear_attention as la
from distributed_pytorch_tpu.ops import paged_attention as pa

#: kind of layer -> what ``stats()`` totals for a model that has such layers
#: (``"latent pages"``: any of the three latent kinds).
TOTALLED = {
    "latent pages": ("decode_page_copies", "decode_pages_in_runs"),
    "gated_delta": ("state_slots_updated", "state_bytes_moved"),
    "cca": ("state_slots_updated", "state_bytes_moved"),
    "latent_sparse": (
        "decode_index_tokens_scored", "decode_index_tokens_fetched",
        "decode_index_tokens_scored_distinct", "decode_kv_tokens_selected",
    ),
    "latent_window": (
        "decode_window_tokens_visible", "decode_window_tokens_read",
    ),
    "attention_window": (
        "decode_window_tokens_visible", "decode_window_tokens_read",
    ),
}


#: ``prefill.chunk`` slice arg -> what ``stats()`` and the ``step`` slice total
#: it as, for the prefill pieces of a model whose full group has K/V layers.
PREFILL_TOTALLED = {
    "keys_walked": "prefill_keys_walked", "keys_table": "prefill_keys_table",
}


def _pool(cache, name: str, layers=None) -> Optional[jax.Array]:
    """The pool ``name`` of the first of ``layers`` (indices of the model's
    blocks; ``None``: any) that keeps one, off the engine's cache tree."""
    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        block, _, layer = str(getattr(path[0], "key", "")).partition("_")
        layer = int(layer) if block == "block" and layer.isdigit() else -1
        if getattr(path[-1], "key", None) == name and (
                layers is None or layer in layers):
            found.setdefault(layer, leaf)
    return found[min(found)] if found else None


class DecodeReads:
    """The plan of a decode model's reads over a cache tree of tables
    ``pages_per_seq`` wide and ``max_slots`` rows (module docstring)."""

    def __init__(self, model, cache, *, max_slots: int, pages_per_seq: int):
        kinds = tuple(getattr(model, "layer_types", None) or ())
        where = lambda *names: [  # noqa: E731
            i for i, kind in enumerate(kinds) if kind in names
        ]
        self.layers = {
            kind: len(where(kind)) for kind in
            ("latent", "latent_sparse", "latent_window", "gated_delta",
             "attention_window", "cca")
        }
        self.layers["latent pages"] = len(where(*LATENT_TYPES))
        # The full group's K/V layers: every layer of a model that names no
        # layer types.
        self.layers["attention"] = (
            len(where(*FULL_KV_TYPES)) if kinds else model.n_layers)
        self.page, self.width = model.page_size, pages_per_seq
        self.num_pages = model.num_pages
        self.whole = max_slots * pages_per_seq * self.page
        self.top_k = self.window = self.state_bytes = 0
        if self.layers["latent_sparse"]:
            self.top_k = model.latent_sizes("latent_sparse")["index_top_k"]
        if self.layers["latent_window"]:
            self.window = model.latent_sizes("latent_window")["window"]
        #: The K/V window layers' window and short table (a decode row's).
        self.kv_window = self.kv_window_pages = 0
        if self.layers["attention_window"]:
            self.kv_window = model.kv_window
            self.kv_window_pages = pa.window_pages(self.kv_window, self.page)
        #: Bytes a decode dispatch moves of state, summed over the layers
        #: that count theirs, for ONE row: each state once in and once out.
        if self.layers["gated_delta"]:
            self.state_bytes = self.layers["gated_delta"] * la.state_bytes_moved(
                1, model.linear_n_heads, model.linear_d_k, model.linear_d_v,
                jnp.dtype(STATE_DTYPE).itemsize,
            )
        if self.layers["cca"]:
            self.state_bytes += 2 * self.layers["cca"] * sum(
                _pool(cache, key, where("cca")).nbytes for key in STATE_KEYS
            ) // max_slots
        #: Whether the decode program returns what its sparse layers selected
        #: (their kernel path sows a list; the gather path masks, keeps none).
        self.selection = bool(
            self.layers["latent_sparse"] and model.paged_kernel
        )
        #: kernel -> pages a block of its call; none on the gather path.
        self.blocks: Dict[str, int] = {}
        #: K/V kernel -> how its call computes a block (what ``stats()``
        #: shows as ``<kernel>_decode_block_form``).
        self.forms: Dict[str, str] = {}
        #: Pages a group shares at least: a block of the latent kernel at the
        #: grouping layers' pool (``None``: no layer groups rows).
        self.group_pages = None
        if model.paged_kernel and (
                pa.resolve_kernel(model.paged_kernel) != "xla"):
            latent = _pool(
                cache, "cached_latent", where("latent", "latent_sparse"))
            if latent is not None:
                self.group_pages = pa.latent_block_pages(pages_per_seq, latent)
            if self.layers["latent"]:
                self.blocks["latent"] = self.group_pages
            if self.layers["latent_sparse"]:
                self.blocks["index"] = pa.index_block_pages(pages_per_seq)
            if self.layers["latent_window"]:
                self.blocks["window"] = pa.latent_block_pages(
                    pa.window_pages(self.window, self.page),
                    _pool(cache, "cached_latent", where("latent_window")),
                )
            # The full group's K/V layers, and the window group's.
            keys = _pool(
                cache, "cached_key", where(*FULL_KV_TYPES) if kinds else None)
            if keys is not None:
                self.blocks["kv"] = pa.kv_block_pages(
                    pages_per_seq, keys, model.dtype)
            if self.kv_window:
                self.blocks["kv_window"] = pa.kv_block_pages(
                    self.kv_window_pages,
                    _pool(cache, "cached_key", where("attention_window")),
                    model.dtype, short=True)
            self.forms = {
                kernel: pa.KV_BLOCK_FORM
                for kernel in ("kv", "kv_window") if kernel in self.blocks
            }
        self.totals = {"decode_rows_grouped": 0}
        for kind, names in TOTALLED.items():
            if self.layers[kind]:
                self.totals.update(dict.fromkeys(names, 0))
        #: Whether a dispatch with no tracer has anything to count.
        self._counted = len(self.totals) > 1
        if self.layers["attention"]:
            self.totals.update(dict.fromkeys(PREFILL_TOTALLED.values(), 0))
        # A model whose layers' state is counted says so on every step slice.
        self._idle = {
            name: 0 for name in TOTALLED["gated_delta"] if name in self.totals
        }
        self._step = dict(self._idle)

    def groups(self, tables, positions):
        """``shared_prefix_groups`` of a dispatch's tables and positions
        (``None``: no kernel that groups), traced in the decode program or on
        the host's staged copies: one rule for both."""
        if self.group_pages is None:
            return None
        return pa.shared_prefix_groups(
            tables, positions, self.page, self.group_pages)

    def operands(self, tables, lens) -> dict:
        """What the decode program tells its latent layers' kernels, worked
        out once for all of them from its table and length operands."""
        groups = self.groups(tables, lens)
        if groups is None:
            return {}
        if self.layers["latent"]:
            groups += (pa.latent_runs(
                tables, lens, *groups, self.page, self.blocks["latent"]),)
        return {"row_groups": groups}

    def prefill_args(
        self, width: int, start: int, tokens: int, traced: bool = False
    ) -> dict:
        """What a ``prefill.chunk`` slice of a piece of ``tokens`` tokens from
        position ``start`` on, padded to ``width``, says beside them; its key
        counts go into the totals and, ``traced``, into the step's own."""
        out = {}
        if self.layers["gated_delta"]:
            out["state_blocks"] = -(-width // min(la.BLOCK, width))
        elif self.layers["cca"]:
            out["state_blocks"] = 1
        if self.layers["attention"]:
            keys = {
                "keys_walked": int(pa.chunk_keys_walked(
                    start + tokens, self.width, self.page)),
                "keys_table": self.width * self.page,
            }
            out.update(keys)
            for sums in [self.totals] + [self._step] * traced:
                for arg, total in PREFILL_TOTALLED.items():
                    sums[total] = sums.get(total, 0) + keys[arg]
        return out

    def count(self, tables, positions, traced: bool = False) -> Dict[str, int]:
        """The counts of one dispatch (module docstring): ``tables [rows,
        pages_per_seq]`` and ``positions [rows]`` of its live rows, in slot
        order (absent rows are in no group, so the live rows group as the
        program's do). ``traced`` adds what only a ``step`` slice carries (the
        ``*_distinct`` counts allocate ``num_pages`` numbers)."""
        rows, page, blocks = len(positions), self.page, self.blocks
        plain, sparse, sliding = (self.layers[kind] for kind in (
            "latent", "latent_sparse", "latent_window"))
        stateful = self.layers["gated_delta"] + self.layers["cca"]
        groups = self.groups(tables, positions)
        visible = int(positions.sum()) + rows
        out, copies, chosen, read = {}, [], 0, 0
        if stateful:
            out["state_slots_updated"] = rows * stateful
            out["state_bytes_moved"] = rows * self.state_bytes
        if sparse:
            out["decode_index_tokens_scored"] = visible
            out["decode_index_tokens_fetched"] = self.whole
            out["decode_kv_tokens_selected"] = chosen = int(
                np.minimum(positions + 1, self.top_k).sum())
            if blocks:
                out["decode_index_tokens_fetched"] = pa.index_tokens_fetched(
                    positions, *groups, page, blocks["index"], self.width)
                copies.append(pa.index_copies_started(
                    tables, positions, *groups, page, blocks["index"]))
        if plain and blocks:
            copies.append(pa.latent_copies_started(
                tables, positions, *groups, page, blocks["latent"]))
        if sliding:
            out["decode_window_tokens_visible"] = int(
                np.minimum(positions + 1, self.window).sum())
            out["decode_window_tokens_read"] = read = int(
                pa.window_tokens_read(positions, self.window, page).sum())
            if blocks:
                windows = pa.window_tables(
                    tables, positions, page, self.window)
                alone = np.arange(rows, dtype=np.int32)
                copies.append(pa.latent_copies_started(
                    *windows[:2], alone, np.zeros_like(alone), page,
                    blocks["window"]))
        if self.kv_window:
            first = pa.window_first_page(positions, self.kv_window, page)
            out["decode_window_tokens_visible"] = out.get(
                "decode_window_tokens_visible", 0) + int(
                np.minimum(positions + 1, self.kv_window).sum())
            out["decode_window_tokens_read"] = out.get(
                "decode_window_tokens_read", 0) + (
                int(pa.kv_tokens_walked(
                    positions - first * page,
                    blocks["kv_window"] * page).sum())
                if blocks else rows * self.kv_window_pages * page)
        if self.layers["latent pages"]:
            out["decode_rows_grouped"] = 0 if groups is None else (
                pa.index_rows_grouped(groups[1], blocks["index"]) if sparse
                else int((groups[1] > 0).sum()))
            out["decode_page_copies"], out["decode_pages_in_runs"] = (
                map(sum, zip(*copies)) if copies else (0, 0))
        if not traced:
            return out
        out["decode_kv_tokens_visible"] = visible
        out["decode_kv_tokens_distinct"] = distinct = self._distinct(
            tables, positions)
        if sparse:
            out["decode_index_tokens_scored_distinct"] = distinct
        if sparse or sliding:
            # A sparse layer reads the latents it selected, a window layer
            # the pages that meet its window: a layer's mean.
            fetched = (sparse * chosen + sliding * read) // (sparse + sliding)
        elif groups is not None:
            fetched = pa.latent_tokens_fetched(
                positions, *groups, page, blocks["latent"], self.width)
        elif blocks:
            fetched = int(
                pa.kv_tokens_walked(positions, blocks["kv"] * page).sum())
        else:
            fetched = self.whole
        out["decode_kv_tokens_fetched"] = fetched
        return out

    def _distinct(self, tables, positions) -> int:
        """Key positions a dispatch's rows could see, each PHYSICAL page
        counted once: rows that share a prefix share its pages, and a kernel
        could serve them all by one read of it. A page counts the most tokens
        any of its rows sees in it (a row at ``pos`` sees ``pos % page + 1``
        of its last page, every earlier one whole)."""
        page = self.page
        last = positions // page  # a row's last live logical page
        seen = np.zeros((self.num_pages,), np.int64)
        whole = np.arange(self.width)[None, :] < last[:, None]
        seen[tables[whole]] = page
        rows = np.arange(len(positions))
        np.maximum.at(seen, tables[rows, last], positions % page + 1)
        return int(seen.sum())

    def note(self, tables, positions, rows, tracer) -> None:
        """Count a dispatch ONCE, the live ``rows`` (in slot order) of the
        staged ``tables`` and ``positions``: into the totals and, under
        ``tracer``, into the step's own and its ``dsa.select`` instant."""
        if not self._counted and not tracer.enabled:
            return  # K/V and S6 / Mamba-2 layers alone: a traced step's only
        tables, positions = tables[rows], positions[rows]
        counts = self.count(tables, positions, tracer.enabled)
        for name in self.totals:
            self.totals[name] += counts.get(name, 0)
        if not tracer.enabled:
            return
        for name, n in counts.items():
            self._step[name] = self._step.get(name, 0) + n
        if self.layers["latent_sparse"]:
            visible = counts["decode_index_tokens_scored"]
            selected = counts["decode_kv_tokens_selected"]
            tracer.instant(
                "dsa.select", rows=len(positions), visible=visible,
                selected=selected, layers=self.layers["latent_sparse"],
                selected_share=selected / max(1, visible),
            )

    def end_step(self) -> Dict[str, int]:
        """What the closing ``step`` slice carries of its dispatches."""
        step, self._step = self._step, dict(self._idle)
        return step
