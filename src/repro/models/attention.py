"""Attention variants: GQA (incl. MHA), MLA (latent), cross-attention.

All functions are pure; caches are explicit pytrees:
  GQA self-attn cache : {"k": (B, S_max, Hkv, Dh), "v": (B, S_max, Hkv, Dh)}
  MLA self-attn cache : {"ckv": (B, S_max, R), "kpe": (B, S_max, Dr)}
  cross-attn cache    : {"k": (B, S_enc, H, Dh), "v": (B, S_enc, H, Dh)}

Modes:
  train   — full-sequence causal (or bidirectional), no cache I/O
  prefill — full-sequence causal, returns the populated cache
  decode  — q_len==1 at position `pos`, reads+updates the cache
  paged_decode — (GQA) q_len==1 over a `PagedKV` page store, read in place
            by the paged-attention kernel; returns the new entry only
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.common import (
    apply_rope,
    dense_init,
    init_norm,
    mrope_cos_sin,
    param_dtype_of,
    rmsnorm,
    rope_cos_sin,
)

Cache = Dict[str, jax.Array]


class PagedKV(NamedTuple):
    """One layer's view of the KV pool's page store for ``paged_decode``:
    the whole ``(L, P, page_size, Hkv, Dh)`` store leaves, the layer to
    read, and the lanes' ``(B, pages_per_seq)`` page tables."""

    k: jax.Array
    v: jax.Array
    layer: jax.Array
    tables: jax.Array

# above this sequence length, causal attention uses the chunked
# online-softmax path (never materializes S x S logits)
FLASH_THRESHOLD = 8192


def _full_attn(q, k, v, *, scale, causal, use_kernel):
    """Dispatch between plain sdpa, chunked flash ref, and the Pallas kernel.

    The flash path pins a sequence-parallel layout: q (and the output) shard
    the seq dim on the plan's seq axis while k/v stay replicated across it —
    every q-block program is then fully local (no per-block K gathers).
    """
    from repro.sharding.ctx import constrain

    S = q.shape[1]
    if use_kernel and causal:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=True)
    if causal and S >= FLASH_THRESHOLD:
        from repro.kernels.ref import flash_attention_ref
        q = constrain(q, "batch", "seq", None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)
        out = flash_attention_ref(q, k, v, causal=True, scale=scale)
        return constrain(out, "batch", "seq", None, None)
    return sdpa(q, k, v, scale=scale, causal=causal)


# ---------------------------------------------------------------------------
# core scaled-dot-product with GQA grouping
# ---------------------------------------------------------------------------


def sdpa(
    q: jax.Array,            # (B, Q, Hq, D)
    k: jax.Array,            # (B, S, Hkv, D)
    v: jax.Array,            # (B, S, Hkv, Dv)
    *,
    scale: float,
    causal: bool,
    q_offset: Optional[jax.Array] = None,   # scalar start position of q
    kv_len: Optional[jax.Array] = None,     # valid kv prefix length (decode)
    extra_logits: Optional[jax.Array] = None,  # (B, Hkv, G, Q, S) additive
) -> jax.Array:
    """Grouped-query attention with fp32 softmax. Returns (B, Q, Hq, Dv)."""
    B, Q, Hq, D = q.shape
    if k.dtype != q.dtype:   # low-precision (fp8) KV cache: upcast for math
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Q, Hkv, G, D)
    logits = jnp.einsum("bqhgd,bshd->bhgqs", qg, k).astype(jnp.float32) * scale
    if extra_logits is not None:
        logits = logits + extra_logits.astype(jnp.float32)

    S = k.shape[1]
    mask = None  # (B or 1, Q, S)
    if causal:
        q_pos = jnp.arange(Q)
        if q_offset is not None:
            q_pos = q_pos + q_offset
        k_pos = jnp.arange(S)
        mask = (k_pos[None, :] <= q_pos[:, None])[None]   # (1, Q, S)
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len)
        if kv_len.ndim == 0:
            valid = (jnp.arange(S)[None, :] < kv_len)[None]        # (1,1,S)
        else:                                             # per-batch (B,)
            valid = jnp.arange(S)[None, None, :] < kv_len[:, None, None]
        mask = valid if mask is None else (mask & valid)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)

    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqs,bshd->bqhgd", w, v)
    return out.reshape(B, Q, Hq, v.shape[-1])


# ---------------------------------------------------------------------------
# GQA layer
# ---------------------------------------------------------------------------


def init_gqa(cfg: ModelConfig, key: jax.Array) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    pd = param_dtype_of(cfg)
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, hq * hd), pd),
        "wk": dense_init(ks[1], (d, hkv * hd), pd),
        "wv": dense_init(ks[2], (d, hkv * hd), pd),
        "wo": dense_init(ks[3], (hq * hd, d), pd, scale=(hq * hd) ** -0.5 / math.sqrt(2 * cfg.num_layers)),
    }


def _positional_cos_sin(cfg: ModelConfig, positions: jax.Array) -> Optional[Tuple[jax.Array, jax.Array]]:
    hd = cfg.resolved_head_dim
    if cfg.pos_type == "rope":
        # positions: (S,) or (B, S)
        return rope_cos_sin(positions, hd, cfg.rope_theta)
    if cfg.pos_type == "mrope":
        # positions: (3, B, S)
        return mrope_cos_sin(positions, hd, cfg.rope_theta, cfg.mrope_sections)
    return None


def gqa_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,                       # (B, S, d)
    *,
    positions: jax.Array,               # rope: (S,)/(B,S); mrope: (3,B,S)
    mode: str = "train",                # train | prefill | decode
    causal: bool = True,
    cache: Optional[Cache] = None,
    pos: Optional[jax.Array] = None,    # decode write position (scalar)
    use_kernel: bool = False,
) -> Tuple[jax.Array, Optional[Cache]]:
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    q = (x @ p["wq"]).reshape(B, S, hq, hd)
    k = (x @ p["wk"]).reshape(B, S, hkv, hd)
    v = (x @ p["wv"]).reshape(B, S, hkv, hd)

    cs = _positional_cos_sin(cfg, positions)
    if cs is not None:
        cos, sin = cs
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    scale = hd ** -0.5
    new_cache: Optional[Cache] = None
    if mode == "train":
        out = (_full_attn(q, k, v, scale=scale, causal=True, use_kernel=use_kernel)
               if causal else sdpa(q, k, v, scale=scale, causal=False))
    elif mode == "prefill":
        new_cache = {"k": k, "v": v}
        out = _full_attn(q, k, v, scale=scale, causal=causal, use_kernel=use_kernel)
    elif mode == "decode":
        assert cache is not None and pos is not None and S == 1
        pos = jnp.asarray(pos)
        k = k.astype(cache["k"].dtype)   # fp8 KV-cache path casts on write
        v = v.astype(cache["v"].dtype)
        if pos.ndim == 0:   # uniform batch position -> contiguous DUS
            k_cache = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
        else:               # per-slot positions (serving engine) -> scatter
            bidx = jnp.arange(B)
            k_cache = cache["k"].at[bidx, pos].set(k[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[bidx, pos].set(v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": k_cache, "v": v_cache}
        out = sdpa(q, k_cache, v_cache, scale=scale, causal=False, kv_len=pos + 1)
    elif mode == "paged_decode":
        # `cache` is a PagedKV; `pos` (B,) counts each lane's entries
        # already in the store. The new entry is attended from here and
        # returned for the caller to write after the layer scan.
        assert isinstance(cache, PagedKV) and S == 1
        from repro.kernels import ops as kops
        k_new = k[:, 0].astype(cache.k.dtype)
        v_new = v[:, 0].astype(cache.v.dtype)
        out = kops.paged_attention(q[:, 0], cache.k, cache.v, k_new, v_new,
                                   pos, cache.tables, cache.layer)[:, None]
        new_cache = {"k": k_new, "v": v_new}
    else:
        raise ValueError(mode)

    out = out.reshape(B, S, hq * hd) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------


def init_cross_attn(cfg: ModelConfig, key: jax.Array) -> dict:
    return init_gqa(cfg, key)


def cross_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,                       # (B, S_dec, d)
    *,
    enc_out: Optional[jax.Array] = None,  # (B, S_enc, d) — train/prefill
    cache: Optional[Cache] = None,        # decode: precomputed enc k/v
) -> Tuple[jax.Array, Optional[Cache]]:
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    q = (x @ p["wq"]).reshape(B, S, hq, hd)
    if cache is None:
        assert enc_out is not None
        k = (enc_out @ p["wk"]).reshape(B, enc_out.shape[1], hkv, hd)
        v = (enc_out @ p["wv"]).reshape(B, enc_out.shape[1], hkv, hd)
        new_cache = {"k": k, "v": v}
    else:
        k, v = cache["k"], cache["v"]
        new_cache = cache
    out = sdpa(q, k, v, scale=hd ** -0.5, causal=False)
    return out.reshape(B, S, hq * hd) @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention) — MiniCPM3 / DeepSeek-V2 style
# ---------------------------------------------------------------------------


def init_mla(cfg: ModelConfig, key: jax.Array) -> dict:
    m = cfg.mla or MLAConfig()
    d, hq = cfg.d_model, cfg.num_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    pd = param_dtype_of(cfg)
    ks = jax.random.split(key, 6)
    return {
        "w_dq": dense_init(ks[0], (d, m.q_lora_rank), pd),
        "q_norm": init_norm(cfg, m.q_lora_rank),
        "w_uq": dense_init(ks[1], (m.q_lora_rank, hq * qk_head), pd),
        "w_dkv": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), pd),
        "kv_norm": init_norm(cfg, m.kv_lora_rank),
        "w_uk": dense_init(ks[3], (m.kv_lora_rank, hq * m.qk_nope_head_dim), pd),
        "w_uv": dense_init(ks[4], (m.kv_lora_rank, hq * m.v_head_dim), pd),
        "wo": dense_init(ks[5], (hq * m.v_head_dim, d), pd,
                         scale=(hq * m.v_head_dim) ** -0.5 / math.sqrt(2 * cfg.num_layers)),
    }


def _mla_q(cfg: ModelConfig, p: dict, x: jax.Array, cos, sin):
    m = cfg.mla or MLAConfig()
    B, S, _ = x.shape
    hq = cfg.num_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    q_lat = rmsnorm(x @ p["w_dq"], p["q_norm"]["scale"], cfg.norm_eps)
    q = (q_lat @ p["w_uq"]).reshape(B, S, hq, qk_head)
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_pe = apply_rope(q_pe, cos, sin)
    return q_nope, q_pe


def _mla_latent_kv(cfg: ModelConfig, p: dict, x: jax.Array, cos, sin):
    m = cfg.mla or MLAConfig()
    ckv_kpe = x @ p["w_dkv"]
    ckv = ckv_kpe[..., : m.kv_lora_rank]
    kpe = ckv_kpe[..., m.kv_lora_rank:]
    ckv = rmsnorm(ckv, p["kv_norm"]["scale"], cfg.norm_eps)
    kpe = apply_rope(kpe[:, :, None, :], cos, sin)[:, :, 0, :]  # single shared head
    return ckv, kpe


def mla_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    *,
    positions: jax.Array,
    mode: str = "train",
    cache: Optional[Cache] = None,
    pos: Optional[jax.Array] = None,
    absorbed_decode: bool = True,
) -> Tuple[jax.Array, Optional[Cache]]:
    """MLA with latent-compressed KV cache.

    Prefill/train use the expanded (materialized K/V) form. Decode defaults
    to the *absorbed* form: queries are projected into the latent space so
    attention runs directly against the (R + Dr)-wide cache — the classic
    MLA serving optimization (cache stays compressed, no per-step K/V
    re-expansion).
    """
    m = cfg.mla or MLAConfig()
    B, S, d = x.shape
    hq = cfg.num_heads
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    # rope over the full qk_rope_head_dim (rot_dim == qk_rope_head_dim)
    cos, sin = rope_cos_sin(positions, m.qk_rope_head_dim, cfg.rope_theta)

    q_nope, q_pe = _mla_q(cfg, p, x, cos, sin)

    if mode in ("train", "prefill"):
        ckv, kpe = _mla_latent_kv(cfg, p, x, cos, sin)
        k_nope = (ckv @ p["w_uk"]).reshape(B, S, hq, m.qk_nope_head_dim)
        v = (ckv @ p["w_uv"]).reshape(B, S, hq, m.v_head_dim)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(kpe[:, :, None, :], (B, S, hq, m.qk_rope_head_dim))], axis=-1)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        if S >= FLASH_THRESHOLD:
            # MLA value dim != qk dim; flash ref handles D_v via padding
            from repro.kernels.ref import flash_attention_ref
            from repro.sharding.ctx import constrain
            dv = m.v_head_dim
            v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                                (0, q.shape[-1] - dv))) if q.shape[-1] != dv else v
            q = constrain(q, "batch", "seq", None, None)
            k = constrain(k, "batch", None, None, None)
            v_pad = constrain(v_pad, "batch", None, None, None)
            out = flash_attention_ref(q, k, v_pad, causal=True, scale=scale)[..., :dv]
            out = constrain(out, "batch", "seq", None, None)
        else:
            out = sdpa(q, k, v, scale=scale, causal=True)
        new_cache = {"ckv": ckv, "kpe": kpe} if mode == "prefill" else None
    elif mode == "decode":
        assert cache is not None and pos is not None and S == 1
        ckv_new, kpe_new = _mla_latent_kv(cfg, p, x, cos, sin)
        ckv_new = ckv_new.astype(cache["ckv"].dtype)
        kpe_new = kpe_new.astype(cache["kpe"].dtype)
        pos = jnp.asarray(pos)
        if pos.ndim == 0:
            ckv = jax.lax.dynamic_update_slice(cache["ckv"], ckv_new, (0, pos, 0))
            kpe = jax.lax.dynamic_update_slice(cache["kpe"], kpe_new, (0, pos, 0))
            valid = (jnp.arange(cache["ckv"].shape[1]) <= pos)[None, None, None, :]
        else:
            bidx = jnp.arange(B)
            ckv = cache["ckv"].at[bidx, pos].set(ckv_new[:, 0].astype(cache["ckv"].dtype))
            kpe = cache["kpe"].at[bidx, pos].set(kpe_new[:, 0].astype(cache["kpe"].dtype))
            valid = (jnp.arange(cache["ckv"].shape[1])[None, :]
                     <= pos[:, None])[:, None, None, :]            # (B,1,1,S)
        new_cache = {"ckv": ckv, "kpe": kpe}
        if ckv.dtype != x.dtype:   # fp8 KV cache: upcast for attention math
            ckv = ckv.astype(x.dtype)
            kpe = kpe.astype(x.dtype)
        S_max = ckv.shape[1]
        if absorbed_decode:
            # q_nope (B,1,H,Dn) @ w_uk per head -> latent query (B,1,H,R)
            w_uk = p["w_uk"].reshape(m.kv_lora_rank, hq, m.qk_nope_head_dim)
            q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)
            logits = (
                jnp.einsum("bqhr,bsr->bhqs", q_lat, ckv)
                + jnp.einsum("bqhd,bsd->bhqs", q_pe, kpe)
            ).astype(jnp.float32) * scale
            logits = jnp.where(valid, logits, -1e30)
            w = jax.nn.softmax(logits, axis=-1).astype(ckv.dtype)
            o_lat = jnp.einsum("bhqs,bsr->bqhr", w, ckv)          # (B,1,H,R)
            w_uv = p["w_uv"].reshape(m.kv_lora_rank, hq, m.v_head_dim)
            out = jnp.einsum("bqhr,rhd->bqhd", o_lat, w_uv)
        else:
            k_nope = (ckv @ p["w_uk"]).reshape(B, S_max, hq, m.qk_nope_head_dim)
            v = (ckv @ p["w_uv"]).reshape(B, S_max, hq, m.v_head_dim)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(kpe[:, :, None, :], (B, S_max, hq, m.qk_rope_head_dim))], axis=-1)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            out = sdpa(q, k, v, scale=scale, causal=False, kv_len=pos + 1)
        out = out.reshape(B, S, hq * m.v_head_dim)
        return out @ p["wo"], new_cache
    else:
        raise ValueError(mode)

    out = out.reshape(B, S, hq * m.v_head_dim)
    return out @ p["wo"], new_cache
