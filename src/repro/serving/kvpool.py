"""Paged KV-cache pool: token-granular KV memory for the serving engine.

The slot-granular engine allocates every request a full ``(1, s_max)``
KV extent for its whole lifetime — a 6-token request on an ``s_max=128``
pool wastes 95% of its slot, and admission is bounded by ``n_slots``
regardless of how short the resident requests are. This module replaces
that layout with a vLLM-style paged pool:

    store        one device pytree shaped like ``model.init_cache(
                 n_pages, page_size)`` — each batch row of the tiny pool
                 is one PAGE holding ``page_size`` tokens of every
                 layer's KV. Page 0 is a reserved scratch page (see
                 below); data pages are 1..n_pages-1.
    page table   per active request, the ordered list of physical pages
                 backing its sequence: token position ``t`` of the
                 request lives at row ``table[t // page_size]``, offset
                 ``t % page_size``.
    alloc/free   `PagedKVPool` hands out pages token-granularly:
                 admission reserves ``ceil(need / page_size)`` pages for
                 the request's worst-case extent (prompt + clamped
                 generation budget) and frees them the step the request
                 retires. OOM fails CLOSED — an admission that does not
                 fit (respecting the free-page watermark) leaves the
                 request queued; nothing is evicted, nothing is dropped.

The decode step stays shape-static (the engines' no-JIT-on-the-serving-
path contract): `gather_pages` assembles the active rows' pages into a
dense ``(B, pages_per_seq * page_size)`` cache, the model's unmodified
``decode_step`` runs on it, and `scatter_token` writes the one new KV
entry per row back through the page table. Gather/scatter are fused into
a single jitted (or AOT-compiled) executable by the engine.

Why garbage pages are harmless (the bitwise-identity argument): a page
table row is padded with page 0 beyond the request's reserved extent,
so the gathered dense cache holds scratch/garbage there — but decode
attention masks every position ``>= pos`` by replacing its logit with
``-1e30`` *before* the fp32 softmax (see ``repro.models.attention``), so
masked lanes contribute exactly-zero weight whether the backing memory
holds zeros or a retired request's stale KV. Token streams are therefore
bitwise identical to the slot-granular engine's whenever the dense shape
matches (``s_max`` a multiple of ``page_size``) — the property
`benchmarks/live_migration.py` and tests/test_kvpool.py gate on.

Paging is sound exactly where padded prefill is: every mixer must index
KV by position (attn/MLA). SSM mixers carry recurrent state with no
sequence dim — there is nothing to page — and enc-dec prefill has its
own shape contract; `supports_paging` excludes both, and the engine
falls back to the slot-granular pool for them (fail-closed, never a
silent wrong answer).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

PyTree = Any

#: batch-axis probe sizes (mirrors `migration.batch_axis_tree`)
_B1, _B2 = 1, 3
#: seq-axis probe sizes — coprime odd sizes that head/rank dims of the
#: reduced configs never collide with on BOTH probes at once
_S1, _S2 = 7, 11

SCRATCH_PAGE = 0


class PoolOOM(RuntimeError):
    """A page allocation does not fit (free pages minus the watermark) —
    the caller must fail closed: leave the request queued, change
    nothing."""


def supports_paging(model) -> bool:
    """Whether the model's KV cache can be paged: every layer's cache
    must be positional (attn/MLA) — SSM recurrent state has no sequence
    dim to page, and enc-dec caches have a second (encoder) sequence
    contract. Mirrors `ServingEngine.supports_padded_prefill`."""
    cfg = model.cfg
    if cfg.encdec is not None:
        return False
    from repro.models.lm import layer_kinds   # local: avoid cycles
    return all(mixer in ("attn", "mla") for mixer, _ in layer_kinds(cfg))


def page_axes(model) -> Tuple[PyTree, PyTree]:
    """Per-leaf ``(page_axis, seq_axis)`` trees of the model's cache
    layout, probed via ``Model.cache_shapes`` (eval_shape — no device
    work). The page axis is the init_cache batch axis (each page is one
    batch row of a ``page_size``-long pool); the sequence axis must sit
    immediately after it for the gather's reshape-merge to be a view.

    Raises:
        ValueError: a leaf has no batch or no sequence axis, or they are
            not adjacent — the model cannot be paged (see
            `supports_paging`).
    """
    b1 = model.cache_shapes(_B1, _S1)
    b2 = model.cache_shapes(_B2, _S1)
    s2 = model.cache_shapes(_B1, _S2)

    def find(a, b, lo, hi):
        for ax in range(a.ndim):
            if a.shape[ax] == lo and b.shape[ax] == hi:
                return ax
        return -1

    pax = jax.tree.map(lambda a, b: find(a, b, _B1, _B2), b1, b2)
    sax = jax.tree.map(lambda a, b: find(a, b, _S1, _S2), b1, s2)

    def check(p, s, leaf):
        if p < 0 or s < 0 or s != p + 1:
            raise ValueError(
                f"cache leaf {leaf.shape} has no pageable (batch, seq) "
                f"axis pair (batch={p}, seq={s}) — this model cannot be "
                "paged (SSM/enc-dec state); use the slot-granular pool")
        return p

    jax.tree.map(check, pax, sax, b1)
    return pax, sax


class PagedKVPool:
    """Token-granular page allocator over one device KV store.

    The pool owns the *bookkeeping* — free list, watermark, per-token
    accounting; the device store it creates (`init_store`) lives on the
    engine as ``engine.cache`` so the existing lifecycle (drain /
    swap_plan device_put / donation through the decode executable) works
    unchanged.

    Args:
        page_size: tokens per page.
        n_pages: DATA pages (the scratch page is allocated on top, so
            the store batch dim is ``n_pages + 1``).
        watermark: free pages an admission must leave behind — headroom
            reserved for in-flight migrations and import bursts. An
            `alloc` that would dip below it raises `PoolOOM` (the
            fail-closed admission gate).
    """

    def __init__(self, page_size: int, n_pages: int, *, watermark: int = 0):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        if watermark < 0 or watermark >= n_pages:
            raise ValueError(
                f"watermark must be in [0, n_pages), got {watermark} "
                f"(n_pages={n_pages})")
        self.page_size = page_size
        self.n_pages = n_pages
        self.watermark = watermark
        # LIFO free list: recently-freed pages are re-used first (their
        # store rows are the warmest)
        self._free: List[int] = list(range(n_pages, 0, -1))

    # -- store ---------------------------------------------------------
    @property
    def store_batch(self) -> int:
        """Batch dim of the device store (data pages + the scratch page)."""
        return self.n_pages + 1

    def init_store(self, model, dtype=jnp.bfloat16) -> PyTree:
        """Build the device store: ``model.init_cache(n_pages + 1,
        page_size)`` — one batch row per page, page 0 scratch."""
        return model.init_cache(self.store_batch, self.page_size, dtype=dtype)

    # -- accounting ----------------------------------------------------
    @property
    def free_pages(self) -> int:
        """Pages currently unallocated (including watermark headroom)."""
        return len(self._free)

    @property
    def admittable_pages(self) -> int:
        """Pages an admission may take without dipping below the
        watermark (migration imports use `alloc(..., reserve=True)` to
        spend the watermark itself)."""
        return max(len(self._free) - self.watermark, 0)

    @property
    def allocated_tokens(self) -> int:
        """Token capacity currently reserved by live requests."""
        return (self.n_pages - len(self._free)) * self.page_size

    def pages_for(self, tokens: int) -> int:
        """Pages needed to back ``tokens`` KV entries."""
        return max(math.ceil(tokens / self.page_size), 1)

    # -- alloc / free --------------------------------------------------
    def alloc(self, n: int, *, reserve: bool = False) -> List[int]:
        """Take ``n`` pages off the free list.

        Args:
            n: pages to allocate.
            reserve: spend the watermark headroom too (migration imports
                — the headroom exists exactly for them); plain admission
                keeps it free.

        Returns:
            The allocated page ids (never `SCRATCH_PAGE`).

        Raises:
            PoolOOM: the pool cannot supply ``n`` pages — nothing is
                allocated (fail closed).
        """
        budget = self.free_pages if reserve else self.admittable_pages
        if n > budget:
            raise PoolOOM(
                f"need {n} pages but only {budget} admittable "
                f"({self.free_pages} free, watermark={self.watermark}, "
                f"n_pages={self.n_pages}) — failing closed")
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: Sequence[int]) -> None:
        """Return pages to the free list.

        Raises:
            ValueError: a page is out of range, the scratch page, or
                already free (double-free — a page-table bookkeeping bug
                that must not be silently absorbed).
        """
        freeing = set(pages)
        if len(freeing) != len(pages):
            raise ValueError(f"duplicate pages in free(): {sorted(pages)}")
        live = set(self._free)
        for p in pages:
            if not 1 <= p <= self.n_pages:
                raise ValueError(f"page {p} out of range [1, {self.n_pages}]")
            if p in live:
                raise ValueError(f"double free of page {p}")
        self._free.extend(pages)


# ---------------------------------------------------------------------------
# gather / scatter (shape-static pytree ops over the page store)
# ---------------------------------------------------------------------------


def gather_pages(store: PyTree, tables: jnp.ndarray,
                 pax: PyTree, sax: PyTree) -> PyTree:
    """Assemble a dense ``(B, pages_per_seq * page_size)`` cache from the
    page store: per leaf, row ``b``'s sequence is the concatenation of
    pages ``tables[b, :]`` (scratch-padded rows gather garbage beyond
    the reserved extent — masked by decode, see the module docstring).

    Args:
        store: the page-store pytree (batch dim = pages).
        tables: ``(B, pages_per_seq)`` int32 physical page ids.
        pax / sax: per-leaf page/seq axis trees (see `page_axes`).
    """
    B, npp = tables.shape

    def one(leaf, p, s):
        g = jnp.take(leaf, tables.reshape(-1), axis=p)
        # (…, B*npp, page_size, …) -> (…, B, npp*page_size, …): the page
        # and seq axes are adjacent (checked by page_axes), so this
        # merge is a reshape of contiguous dims
        shape = (leaf.shape[:p] + (B, npp * leaf.shape[s])
                 + leaf.shape[s + 1:])
        return g.reshape(shape)

    return jax.tree.map(one, store, pax, sax)


def scatter_token(store: PyTree, dense: PyTree, tables: jnp.ndarray,
                  pos: jnp.ndarray, pax: PyTree, sax: PyTree) -> PyTree:
    """Write each row's newest KV entry (position ``pos[b]`` of the
    dense cache) back into its page: physical page ``tables[b, pos[b] //
    page_size]``, offset ``pos[b] % page_size``. Rows whose table entry
    is the scratch page (inactive lanes) write garbage into page 0 —
    harmless by construction.
    """

    def one(leaf, d, p, s):
        ps = leaf.shape[s]
        idx = pos // ps                                   # (B,) page slot
        phys = jnp.take_along_axis(tables, idx[:, None], axis=1)[:, 0]
        off = pos % ps                                    # (B,) in-page
        # each row's entry at its own pos: the index lives on the PAGE
        # (row) axis and selects one seq position per row
        sel = pos.reshape((1,) * p + (-1,) + (1,) * (d.ndim - p - 1))
        tok = jnp.take_along_axis(d, sel, axis=s)         # seq dim -> 1
        tok = jnp.squeeze(tok, axis=s)
        ix = (slice(None),) * p + (phys, off)
        return leaf.at[ix].set(tok.astype(leaf.dtype))

    return jax.tree.map(one, store, dense, pax, sax)


def write_pages(store: PyTree, single: PyTree, pages: Sequence[int],
                pax: PyTree, sax: PyTree) -> PyTree:
    """Write a single-sequence cache (batch dim 1 — a prefill result or
    a fitted migration snapshot) into the store at ``pages``: the seq
    dim is padded/truncated to ``len(pages) * page_size``, split into
    page-sized rows, and scattered. Entries of ``pages`` equal to
    `SCRATCH_PAGE` absorb the slack (import writes full-width tables
    whose tail is scratch — shape-static, one compiled op). ``pages``
    may be a traced ``(n,)`` int32 array (see `make_paged_prefill`).
    """
    pages_arr = jnp.asarray(pages, jnp.int32)
    n = len(pages)

    def one(leaf, c, p, s):
        ps = leaf.shape[s]
        target = n * ps
        if c.shape[s] > target:
            c = jax.lax.slice_in_dim(c, 0, target, axis=s)
        elif c.shape[s] < target:
            pad = [(0, 0)] * c.ndim
            pad[s] = (0, target - c.shape[s])
            c = jnp.pad(c, pad)
        # (…, 1, n*ps, …) -> (…, n, ps, …): batch(=1) and seq axes merge
        shape = c.shape[:p] + (n, ps) + c.shape[s + 1:]
        c = c.reshape(shape).astype(leaf.dtype)
        ix = (slice(None),) * p + (pages_arr,)
        return leaf.at[ix].set(c)

    return jax.tree.map(one, store, single, pax, sax)


def make_paged_decode(model, pax: PyTree, sax: PyTree):
    """The fused paged decode step (one jittable function — the engine's
    AOT unit): gather the active rows' pages into a dense cache, run the
    model's unmodified ``decode_step``, scatter the one new token per
    row back through the page tables.

    Signature (cache at position 2, matching the slot engine's
    ``donate_argnums=(2,)`` contract so the store is donated through
    every step): ``(params, tokens (B,1), store, pos (B,), tables
    (B, pages_per_seq)) -> (logits, new_store)``.
    """

    def paged_decode(params, tokens, store, pos, tables):
        dense = gather_pages(store, tables, pax, sax)
        logits, dense = model.decode_step(params, tokens, dense, pos)
        return logits, scatter_token(store, dense, tables, pos, pax, sax)

    return paged_decode


def make_paged_prefill(model, pax: PyTree, sax: PyTree):
    """The fused paged prefill (the engine's per-prompt-shape AOT unit):
    run the model's single-sequence ``prefill`` and write its cache into
    the request's pages inside the same executable, so an admission
    runs no eager device op that could compile on the serving path.

    Signature (store at position 2, donated like decode's):
    ``(params, batch, store, row (pages_per_seq,)) -> (logits, new_store)``.
    """

    def paged_prefill(params, batch, store, row):
        logits, single = model.prefill(params, batch)
        return logits, write_pages(store, single, row, pax, sax)

    return paged_prefill
