"""Unified model facade over the decoder-only and enc-dec assemblies."""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, lm

PyTree = Any


class Model:
    """Pure-function bundle for one architecture.

    All methods are jit/pjit-compatible; nothing here touches device state.
    """

    def __init__(self, cfg: ModelConfig, *, remat_policy: Optional[str] = "nothing",
                 loss_chunk: Optional[int] = None, use_kernel: bool = False):
        self.cfg = cfg
        self.remat_policy = remat_policy
        self.loss_chunk = loss_chunk
        self.use_kernel = use_kernel
        self._is_encdec = cfg.encdec is not None

    # ---- params ----
    def init_params(self, key: jax.Array, max_seq: Optional[int] = None) -> PyTree:
        if self._is_encdec:
            return encdec.init_params(self.cfg, key, max_seq=max_seq)
        return lm.init_params(self.cfg, key)

    def param_shapes(self, max_seq: Optional[int] = None) -> PyTree:
        key = jax.random.PRNGKey(0)
        return jax.eval_shape(lambda k: self.init_params(k, max_seq=max_seq), key)

    # ---- training ----
    def train_loss(self, params: PyTree, batch: Dict[str, jax.Array]
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        if self._is_encdec:
            return encdec.train_loss(self.cfg, params, batch,
                                     loss_chunk=self.loss_chunk)
        return lm.train_loss(self.cfg, params, batch,
                             loss_chunk=self.loss_chunk,
                             remat_policy=self.remat_policy,
                             use_kernel=self.use_kernel)

    # ---- serving ----
    def prefill(self, params: PyTree, batch: Dict[str, jax.Array]
                ) -> Tuple[jax.Array, PyTree]:
        if self._is_encdec:
            return encdec.prefill(self.cfg, params, batch)
        return lm.prefill(self.cfg, params, batch, use_kernel=self.use_kernel)

    def decode_step(self, params: PyTree, tokens: jax.Array, cache: PyTree,
                    pos: jax.Array) -> Tuple[jax.Array, PyTree]:
        if self._is_encdec:
            return encdec.decode_step(self.cfg, params, tokens, cache, pos)
        return lm.decode_step(self.cfg, params, tokens, cache, pos)

    def paged_decode_step(self, params: PyTree, tokens: jax.Array,
                          store: PyTree, pos: jax.Array, tables: jax.Array
                          ) -> Tuple[jax.Array, PyTree]:
        if self._is_encdec:
            raise ValueError("paged decode is for decoder-only models")
        return lm.paged_decode_step(self.cfg, params, tokens, store, pos,
                                    tables)

    def init_cache(self, batch: int, s_max: int, dtype=jnp.bfloat16,
                   enc_len: Optional[int] = None) -> PyTree:
        if self._is_encdec:
            return encdec.init_cache(self.cfg, batch, s_max,
                                     enc_len=enc_len or self.cfg.encdec.encoder_seq_len,
                                     dtype=dtype)
        return lm.init_cache(self.cfg, batch, s_max, dtype=dtype)

    def cache_shapes(self, batch: int, s_max: int, dtype=jnp.bfloat16,
                     enc_len: Optional[int] = None) -> PyTree:
        return jax.eval_shape(
            lambda: self.init_cache(batch, s_max, dtype=dtype, enc_len=enc_len))


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
