"""Pallas TPU paged decode attention over the KV pool's page store.

One decode query per lane attends over that lane's pages of one layer of
the store, read in place through its page table, plus the lane's new
K/V entry (not yet written to the store):

    q          (B, Hq, D)                  the lanes' queries
    k/v pages  (L, P, page_size, Hkv, D)   the whole store leaf (page-major,
                                           as `repro.serving.kvpool` keeps it)
    k/v new    (B, Hkv, D)                 each lane's entry at ``lengths[b]``
    lengths    (B,)                        entries already in the store
    tables     (B, pages_per_seq)          physical page ids per lane
    layer      ()                          which layer of the leaf to read

Grid: (B,), one program per lane with every head. The program copies only
the lane's first ``ceil(lengths[b] / page_size)`` pages, whole pages at a
time (one DMA each for K and V), into a double-buffered VMEM block of
``pages_per_block`` pages, and runs an fp32 online softmax over the
blocks as `flash_attention` does. A page holds ``page_size * Hkv`` rows
of ``D`` (a position's heads are adjacent), so a block is one
``(rows, D)`` matrix: the logits of all ``Hq`` query heads against every
row come from one matmul, and a constant mask keeps the rows of each
query head's own kv head (GQA groups of ``Hq // Hkv``) and the positions
below ``lengths[b]``. The new entry is the last logit column, taken from
registers. A lane of length 0 copies no page.

The buffers are zeroed once per call, so the rows of a block past the
lane's last page hold finite values from earlier blocks; they are masked
like every position at or beyond ``lengths[b]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_PAGES_PER_BLOCK = 8


def _paged_kernel(layer_ref, len_ref, tbl_ref,                 # prefetch
                  q_ref, knew_ref, vnew_ref, head_ref, k_hbm, v_hbm,
                  o_ref,
                  kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *,
                  scale: float, page_size: int, hkv: int, bp: int, rows: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = len_ref[b]
    n_pages = (length + page_size - 1) // page_size
    n_blk = (n_pages + bp - 1) // bp

    @pl.when(b == 0)
    def _zero():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(blk, slot, j):
        page = tbl_ref[b, blk * bp + j]
        dst = pl.ds(j * rows, rows)
        return (pltpu.make_async_copy(k_hbm.at[layer, page],
                                      kbuf.at[slot, dst], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      vbuf.at[slot, dst], sem.at[1, slot]))

    def each_page(blk, slot, fn):
        def one(j, carry):
            for c in copies(blk, slot, j):
                fn(c)
            return carry
        jax.lax.fori_loop(0, jnp.minimum(bp, n_pages - blk * bp), one, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_blk > 0)
    def _first():
        each_page(0, 0, lambda c: c.start())

    q = q_ref[...]                                           # (Hq, D)
    head_ok = head_ref[...] != 0                             # (Hq, R)

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blk)
        def _next():
            each_page(blk + 1, 1 - slot, lambda c: c.start())

        each_page(blk, slot, lambda c: c.wait())
        k = kbuf[slot].astype(q.dtype)                       # (R, D)
        v = vbuf[slot].astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (Hq, R)
        # row r of the block is position r // hkv of it, kv head r % hkv
        left = (length - blk * bp * page_size) * hkv
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(head_ok & (col < left), s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_blk, block, 0)

    # the lane's new entry: one more logit column per query head
    qf = q.astype(jnp.float32)
    s_new = jnp.sum(qf * knew_ref[...].astype(jnp.float32), axis=-1,
                    keepdims=True) * scale                   # (Hq, 1)
    m_prev = m_ref[...]
    m_fin = jnp.maximum(m_prev, s_new)
    corr = jnp.exp(m_prev - m_fin)
    p_new = jnp.exp(s_new - m_fin)
    l_fin = l_ref[...] * corr + p_new
    acc = acc_ref[...] * corr + p_new * vnew_ref[...].astype(jnp.float32)
    o_ref[...] = (acc / l_fin).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,            # (B, Hq, D)
    k_pages: jax.Array,      # (L, P, page_size, Hkv, D)
    v_pages: jax.Array,      # (L, P, page_size, Hkv, D)
    k_new: jax.Array,        # (B, Hkv, D)
    v_new: jax.Array,        # (B, Hkv, D)
    lengths: jax.Array,      # (B,) int32
    tables: jax.Array,       # (B, pages_per_seq) int32
    layer: jax.Array,        # () int32
    *,
    pages_per_block: int = DEFAULT_PAGES_PER_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention of each lane over its live pages and its new
    entry; returns (B, Hq, D) in ``q.dtype``."""
    L, P, page_size, hkv, D = k_pages.shape
    B, hq, _ = q.shape
    assert hq % hkv == 0, (hq, hkv)
    G = hq // hkv
    pps = tables.shape[1]
    bp = min(pages_per_block, pps)
    rows = page_size * hkv
    R = bp * rows

    # (page_size, Hkv) merge into one row axis: a reshape of contiguous
    # dims, so the store is not copied
    kf = k_pages.reshape(L, P, rows, D)
    vf = v_pages.reshape(L, P, rows, D)
    head = ((jnp.arange(R) % hkv)[None, :]
            == (jnp.arange(hq) // G)[:, None]).astype(jnp.int32)
    knew = jnp.repeat(k_new, G, axis=1)                      # (B, Hq, D)
    vnew = jnp.repeat(v_new, G, axis=1)

    lane = lambda b, *_: (b, 0, 0)                           # noqa: E731
    kernel = functools.partial(_paged_kernel, scale=D ** -0.5,
                               page_size=page_size, hkv=hkv, bp=bp, rows=rows)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, hq, D), lane),
                pl.BlockSpec((None, hq, D), lane),
                pl.BlockSpec((None, hq, D), lane),
                pl.BlockSpec((hq, R), lambda b, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, hq, D), lane),
            scratch_shapes=[
                pltpu.VMEM((2, R, D), k_pages.dtype),
                pltpu.VMEM((2, R, D), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hq, 1), jnp.float32),            # running max
                pltpu.VMEM((hq, 1), jnp.float32),            # running sum
                pltpu.VMEM((hq, D), jnp.float32),            # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((B, hq, D), q.dtype),
        # sequential lanes: the buffers are zeroed by lane 0 only
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      tables.astype(jnp.int32), q, knew, vnew, head, kf, vf)
