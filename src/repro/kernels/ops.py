"""jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile to Mosaic. On CPU they execute in interpret
mode — the kernel body runs op-by-op, which validates BlockSpec indexing
and the online-softmax/recurrence logic but not what Mosaic accepts (see
tests/test_tpu_compile.py for that). Any other backend is refused: a
kernel is never silently interpreted where it was meant to compile.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import moe_dispatch as _moe
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels compile on TPU or interpret on CPU; "
                       f"backend {backend!r} is neither")


@functools.partial(jax.jit, static_argnames=("causal", "scale", "q_block", "k_block"))
def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    q_block: int = _fa.DEFAULT_Q_BLOCK,
                    k_block: int = _fa.DEFAULT_K_BLOCK) -> jax.Array:
    return _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                               q_block=q_block, k_block=k_block,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B_mat, C_mat, *, chunk: int = 256
             ) -> Tuple[jax.Array, jax.Array]:
    return _ssd.ssd_scan(x, dt, A, B_mat, C_mat, chunk=chunk,
                         interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("k", "norm_topk", "block"))
def moe_topk(logits, k: int, *, norm_topk: bool = False,
             block: int = _moe.DEFAULT_BLOCK) -> Tuple[jax.Array, jax.Array]:
    return _moe.moe_topk(logits, k, norm_topk=norm_topk, block=block,
                         interpret=_interpret())
