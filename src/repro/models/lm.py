"""Decoder-only LM assembly (dense / MoE / SSM / hybrid families).

Layers are scan-stacked (leading L dim on every layer param / cache leaf)
so the traced graph contains ONE layer body regardless of depth — essential
for fast lowering of 96-layer configs and for clean pjit partitioning.

Hybrid (Jamba) models scan over *periods*: one period = `hybrid_period`
explicit sub-layers (attention at `hybrid_attn_offsets`, Mamba elsewhere;
MoE per the MoEConfig cadence).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mlp as ffn
from repro.models import ssm as ssd
from repro.models.common import (
    apply_norm,
    embed_init,
    init_norm,
    padded_vocab,
    param_dtype_of,
    vocab_mask,
)
from repro.sharding.ctx import constrain

PyTree = Any


# ---------------------------------------------------------------------------
# per-position layer kinds
# ---------------------------------------------------------------------------


def layer_kinds(cfg: ModelConfig) -> Tuple[Tuple[str, str], ...]:
    """(mixer_kind, ffn_kind) for each in-period position (or the single
    repeated layer for homogeneous models)."""
    period = cfg.hybrid_period or 1
    kinds = []
    for off in range(period):
        if cfg.family == "ssm":
            mixer = "ssm"
        elif cfg.hybrid_period:
            mixer = "attn" if off in cfg.hybrid_attn_offsets else "ssm"
        else:
            mixer = "mla" if cfg.attn_type == "mla" else "attn"
        if cfg.family == "ssm":
            f = "none"
        elif cfg.moe is not None and (off % cfg.moe.every_k_layers == cfg.moe.offset):
            f = "moe"
        else:
            f = "mlp"
        kinds.append((mixer, f))
    return tuple(kinds)


def n_scan_steps(cfg: ModelConfig) -> int:
    period = cfg.hybrid_period or 1
    assert cfg.num_layers % period == 0, (cfg.num_layers, period)
    return cfg.num_layers // period


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_sublayer(cfg: ModelConfig, key: jax.Array, mixer: str, f: str) -> dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"mixer_norm": init_norm(cfg, cfg.d_model)}
    if mixer == "attn":
        p["mixer"] = attn.init_gqa(cfg, ks[0])
    elif mixer == "mla":
        p["mixer"] = attn.init_mla(cfg, ks[0])
    else:
        p["mixer"] = ssd.init_ssm(cfg, ks[0])
    if f != "none":
        p["ffn_norm"] = init_norm(cfg, cfg.d_model)
        p["ffn"] = ffn.init_moe(cfg, ks[1]) if f == "moe" else ffn.init_mlp(cfg, ks[1])
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> PyTree:
    pd = param_dtype_of(cfg)
    kinds = layer_kinds(cfg)
    steps = n_scan_steps(cfg)
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def one_step(k):
        sub_keys = jax.random.split(k, len(kinds))
        if cfg.hybrid_period:
            return {f"pos{off}": _init_sublayer(cfg, sk, *kinds[off])
                    for off, sk in enumerate(sub_keys)}
        return _init_sublayer(cfg, sub_keys[0], *kinds[0])

    layer_keys = jax.random.split(k_layers, steps)
    layers = jax.vmap(one_step)(layer_keys)

    v_pad = padded_vocab(cfg.vocab_size)
    params: Dict[str, Any] = {
        "embed": embed_init(k_embed, (v_pad, cfg.d_model), pd),
        "layers": layers,
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(k_head, (cfg.d_model, v_pad), pd)
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, s_max: int, dtype=jnp.bfloat16) -> PyTree:
    """Zeroed decode cache, scan-stacked over layers/periods."""
    steps = n_scan_steps(cfg)
    kinds = layer_kinds(cfg)

    def sub_cache(mixer: str) -> PyTree:
        if mixer == "attn":
            hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            return {"k": jnp.zeros((steps, batch, s_max, hkv, hd), dtype),
                    "v": jnp.zeros((steps, batch, s_max, hkv, hd), dtype)}
        if mixer == "mla":
            m = cfg.mla
            return {"ckv": jnp.zeros((steps, batch, s_max, m.kv_lora_rank), dtype),
                    "kpe": jnp.zeros((steps, batch, s_max, m.qk_rope_head_dim), dtype)}
        s = cfg.ssm
        d_in, H, P, N, _ = ssd.ssm_dims(cfg)
        gn = s.n_groups * s.d_state
        return {"conv_x": jnp.zeros((steps, batch, s.d_conv - 1, d_in), dtype),
                "conv_B": jnp.zeros((steps, batch, s.d_conv - 1, gn), dtype),
                "conv_C": jnp.zeros((steps, batch, s.d_conv - 1, gn), dtype),
                "ssm": jnp.zeros((steps, batch, H, P, N), jnp.float32)}

    if cfg.hybrid_period:
        return {f"pos{off}": sub_cache(kinds[off][0]) for off in range(len(kinds))}
    return sub_cache(kinds[0][0])


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _run_sublayer(
    cfg: ModelConfig,
    p: dict,
    kind: Tuple[str, str],
    x: jax.Array,
    *,
    positions: jax.Array,
    mode: str,
    cache: Optional[PyTree],
    pos: Optional[jax.Array],
    use_kernel: bool,
) -> Tuple[jax.Array, Optional[PyTree], jax.Array]:
    mixer, f = kind
    sp = "sp" if mode == "train" else None
    h = apply_norm(cfg, p["mixer_norm"], x)
    if mixer == "attn":
        out, new_cache = attn.gqa_attention(
            cfg, p["mixer"], h, positions=positions, mode=mode,
            cache=cache, pos=pos, use_kernel=use_kernel)
    elif mixer == "mla":
        out, new_cache = attn.mla_attention(
            cfg, p["mixer"], h, positions=positions, mode=mode,
            cache=cache, pos=pos)
    else:
        out, new_cache = ssd.ssm_block(
            cfg, p["mixer"], h, mode=mode,
            state=cache, use_kernel=use_kernel)
    # pin the TP partial-sum output to the sequence-parallel layout BEFORE
    # the residual add: the cross-model reduction lowers to reduce-scatter
    # instead of all-reduce (halves activation wire bytes under SP)
    x = x + constrain(out, "batch", sp, None)

    aux = jnp.zeros((), jnp.float32)
    if f != "none":
        h = apply_norm(cfg, p["ffn_norm"], x)
        if f == "moe":
            out, aux = ffn.moe_ffn(cfg, p["ffn"], h, use_kernel=use_kernel)
        else:
            out = ffn.mlp(cfg, p["ffn"], h)
        x = x + constrain(out, "batch", sp, None)
    return x, new_cache, aux


def _remat_policy(name: Optional[str]):
    """Map a policy name to a jax.checkpoint policy.

    "nothing" (baseline): save only the scan carry — minimum memory,
    full forward recompute in backward (~1.33x flops).
    "dots": additionally save matmul outputs — less recompute, more memory.
    """
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name in (None, "nothing"):
        return jax.checkpoint_policies.nothing_saveable
    raise ValueError(f"unknown remat policy {name!r}")


def forward(
    cfg: ModelConfig,
    params: PyTree,
    tokens: jax.Array,                 # (B, S) int32
    *,
    mode: str = "train",               # train | prefill | decode
    positions: Optional[jax.Array] = None,
    cache: Optional[PyTree] = None,
    pos: Optional[jax.Array] = None,   # decode position (scalar int32)
    remat: bool = True,
    remat_policy: Optional[str] = "nothing",
    use_kernel: bool = False,
) -> Tuple[jax.Array, Optional[PyTree], jax.Array]:
    """Returns (hidden (B,S,d), new_cache, moe_aux_sum)."""
    B, S = tokens.shape
    kinds = layer_kinds(cfg)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.activ_dtype))
    x = constrain(x, "batch", "sp" if mode == "train" else None, None)

    if positions is None:
        if mode == "decode":
            p = jnp.asarray(pos, dtype=jnp.int32)
            positions = (jnp.full((B, 1), p) if p.ndim == 0
                         else p[:, None])                # per-slot positions
        else:
            positions = jnp.arange(S, dtype=jnp.int32)
        if cfg.pos_type == "mrope":
            positions = jnp.broadcast_to(positions, (3, B, S)) if positions.ndim == 2 \
                else jnp.broadcast_to(positions[None, None, :], (3, B, S))

    want_cache = mode in ("prefill", "decode")

    def body_fn(x, step_in):
        lp, lc = step_in
        aux_total = jnp.zeros((), jnp.float32)
        if cfg.hybrid_period:
            new_lc = {}
            for off, kind in enumerate(kinds):
                sub_c = lc[f"pos{off}"] if lc is not None else None
                x, sc, aux = _run_sublayer(
                    cfg, lp[f"pos{off}"], kind, x,
                    positions=positions, mode=mode, cache=sub_c, pos=pos,
                    use_kernel=use_kernel)
                x = constrain(x, "batch", "sp" if mode == "train" else None, None)
                new_lc[f"pos{off}"] = sc
                aux_total = aux_total + aux
        else:
            x, new_lc, aux = _run_sublayer(
                cfg, lp, kinds[0], x,
                positions=positions, mode=mode, cache=lc, pos=pos,
                use_kernel=use_kernel)
            x = constrain(x, "batch", "sp" if mode == "train" else None, None)
            aux_total = aux_total + aux
        return x, (new_lc, aux_total)

    if remat:
        body_fn = jax.checkpoint(body_fn, policy=_remat_policy(remat_policy),
                                 prevent_cse=False)

    xs = (params["layers"], cache) if want_cache else (params["layers"], None)
    if not want_cache:
        # scan without cache leaves: thread params only
        def body_nocache(x, lp):
            return body_fn(x, (lp, None))
        x, (new_cache, aux_steps) = jax.lax.scan(body_nocache, x, params["layers"])
        new_cache = None
    else:
        x, (new_cache, aux_steps) = jax.lax.scan(body_fn, x, xs)

    x = apply_norm(cfg, params["final_norm"], x)
    return x, new_cache, jnp.sum(aux_steps)


def logits_fn(cfg: ModelConfig, params: PyTree, hidden: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return hidden @ params["embed"].T
    return hidden @ params["lm_head"]


# ---------------------------------------------------------------------------
# losses / serving entry points
# ---------------------------------------------------------------------------


def cross_entropy(
    cfg: ModelConfig,
    params: PyTree,
    hidden: jax.Array,     # (B, S, d)
    targets: jax.Array,    # (B, S) int32
    mask: Optional[jax.Array] = None,
    chunk: Optional[int] = None,
) -> jax.Array:
    """Token-mean next-token CE with fp32 log-softmax.

    `chunk` chunks the sequence axis so the (B, S, V) logits tensor is never
    materialized (critical for 256k vocabs at train shapes).
    """
    B, S, d = hidden.shape
    if mask is None:
        mask = jnp.ones((B, S), dtype=jnp.float32)

    v_pad = padded_vocab(cfg.vocab_size)
    vmask = (vocab_mask(cfg.vocab_size, v_pad)
             if v_pad != cfg.vocab_size else None)

    def chunk_loss(h, t, m):
        logits = logits_fn(cfg, params, h).astype(jnp.float32)
        if vmask is not None:
            logits = logits + vmask
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * m)

    if chunk is None or chunk >= S:
        total = chunk_loss(hidden, targets, mask)
    else:
        assert S % chunk == 0
        nc = S // chunk
        hc = hidden.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
        tc = targets.reshape(B, nc, chunk).transpose(1, 0, 2)
        mc = mask.reshape(B, nc, chunk).transpose(1, 0, 2)

        def body(acc, inp):
            h, t, m = inp
            return acc + jax.checkpoint(chunk_loss)(h, t, m), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, tc, mc))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def train_loss(
    cfg: ModelConfig,
    params: PyTree,
    batch: Dict[str, jax.Array],
    *,
    aux_weight: float = 0.01,
    loss_chunk: Optional[int] = None,
    remat_policy: Optional[str] = "nothing",
    use_kernel: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    tokens = batch["tokens"]
    positions = batch.get("positions")
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    if positions is not None:
        positions = positions[..., :-1]
    hidden, _, aux = forward(
        cfg, params, inp, mode="train", positions=positions,
        remat_policy=remat_policy, use_kernel=use_kernel)
    ce = cross_entropy(cfg, params, hidden, tgt,
                       mask=batch.get("loss_mask"), chunk=loss_chunk)
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "moe_aux": aux}


def prefill(
    cfg: ModelConfig,
    params: PyTree,
    batch: Dict[str, jax.Array],
    *,
    use_kernel: bool = False,
) -> Tuple[jax.Array, PyTree]:
    """Returns (last-token logits (B, V), populated cache).

    ``batch`` may carry ``true_len`` (scalar int32): the prompt is then
    treated as right-padded to the token buffer's length and the logits
    are read at position ``true_len - 1`` instead of the last position.
    With causal attention the positions below ``true_len`` never see the
    padding, so a padded-bucket prefill is bit-for-bit equivalent at the
    read position — this is what lets serving engines compile a few
    bucket shapes instead of one executable per prompt length.
    """
    tokens = batch["tokens"]
    hidden, cache, _ = forward(
        cfg, params, tokens, mode="prefill",
        positions=batch.get("positions"), remat=False, use_kernel=use_kernel)
    true_len = batch.get("true_len")
    if true_len is None:
        last = hidden[:, -1:, :]
    else:
        last = jax.lax.dynamic_slice_in_dim(hidden, true_len - 1, 1, axis=1)
    logits = logits_fn(cfg, params, last)[:, 0, :]
    return logits, cache


def decode_step(
    cfg: ModelConfig,
    params: PyTree,
    tokens: jax.Array,      # (B, 1)
    cache: PyTree,
    pos: jax.Array,         # scalar int32 — current write position
) -> Tuple[jax.Array, PyTree]:
    """One serving step: returns (logits (B, V), updated cache)."""
    hidden, new_cache, _ = forward(
        cfg, params, tokens, mode="decode", cache=cache, pos=pos, remat=False)
    logits = logits_fn(cfg, params, hidden[:, 0:1, :])[:, 0, :]
    return logits, new_cache


def paged_decode_step(
    cfg: ModelConfig,
    params: PyTree,
    tokens: jax.Array,      # (B, 1)
    store: PyTree,          # {"k", "v"}: (L, P, page_size, Hkv, Dh) pages
    pos: jax.Array,         # (B,) int32 — entries already in the store
    tables: jax.Array,      # (B, pages_per_seq) int32
) -> Tuple[jax.Array, PyTree]:
    """One serving step over a paged KV store (GQA models): each layer's
    attention reads the lanes' pages in place (`attention.PagedKV`), so
    the store is closed over by the layer scan, never sliced or restacked
    by it. Returns (logits (B, V), the new entries ``{"k", "v"}`` each
    (L, B, Hkv, Dh)); the caller writes them into the store.
    """
    B = tokens.shape[0]
    kinds = layer_kinds(cfg)
    assert len(kinds) == 1 and kinds[0][0] == "attn", kinds
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.activ_dtype))
    x = constrain(x, "batch", None, None)
    pos = jnp.asarray(pos, dtype=jnp.int32)
    positions = pos[:, None]
    if cfg.pos_type == "mrope":
        positions = jnp.broadcast_to(positions, (3, B, 1))

    def body(x, step_in):
        lp, layer = step_in
        kv = attn.PagedKV(store["k"], store["v"], layer, tables)
        x, new_kv, _ = _run_sublayer(
            cfg, lp, kinds[0], x, positions=positions, mode="paged_decode",
            cache=kv, pos=pos, use_kernel=True)
        return constrain(x, "batch", None, None), new_kv

    layers = jnp.arange(n_scan_steps(cfg), dtype=jnp.int32)
    x, new_kv = jax.lax.scan(body, x, (params["layers"], layers))
    x = apply_norm(cfg, params["final_norm"], x)
    return logits_fn(cfg, params, x)[:, 0, :], new_kv
