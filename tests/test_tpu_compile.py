"""Compile the main-path kernels and the full-width paged decode step for a
described TPU v5e chip (no chip attached): what Mosaic or the TPU
compiler refuses, or what does not fit a chip's memory, fails here.

The topology is described inside a module-scoped fixture (never at
import): only the worker that runs this file loads the TPU compiler.
The persistent compilation cache is off around these compiles — an
entry written for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as _fa
from repro.kernels import moe_dispatch as _moe
from repro.kernels import ops as _ops
from repro.kernels import ssd_scan as _ssd
from repro.models import build_model
from repro.serving import kvpool

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 - any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    if old_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mosaic(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_minitron_widths(one_chip):
    for S in (512, 37):
        q = _sds(one_chip, (1, S, 24, 128), jnp.bfloat16)
        kv = _sds(one_chip, (1, S, 8, 128), jnp.bfloat16)
        compiled = jax.jit(lambda q, k, v: _fa.flash_attention(
            q, k, v, causal=True)).lower(q, kv, kv).compile()
        assert _mosaic(compiled), S


def test_moe_topk_compiles_at_qwen2_moe_widths(one_chip):
    logits = _sds(one_chip, (512, 60), jnp.float32)
    compiled = jax.jit(lambda x: _moe.moe_topk(x, 4)).lower(logits).compile()
    assert _mosaic(compiled)


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    """mamba2-370m: 32 heads x 64, one group of state 128, chunk 256."""
    f = jax.jit(lambda x, dt, A, B, C: _ssd.ssd_scan(x, dt, A, B, C,
                                                     chunk=256))
    compiled = f.lower(_sds(one_chip, (1, 512, 32, 64), jnp.bfloat16),
                       _sds(one_chip, (1, 512, 32), jnp.float32),
                       _sds(one_chip, (32,), jnp.float32),
                       _sds(one_chip, (1, 512, 1, 128), jnp.bfloat16),
                       _sds(one_chip, (1, 512, 1, 128), jnp.bfloat16)
                       ).compile()
    assert _mosaic(compiled)


def test_minitron_paged_decode_fits_one_chip(one_chip):
    """The serving engine's paged decode at full minitron-4b width (bf16,
    8 lanes, s_max 512, page 16) fits one v5e chip's HBM."""
    model = build_model(get_config("minitron_4b"))
    n_slots, s_max, page = 8, 512, 16
    pages_per_seq = s_max // page
    n_store = n_slots * pages_per_seq + 1          # data pages + scratch
    pax, sax = kvpool.page_axes(model)
    place = lambda t: jax.tree.map(                # noqa: E731
        lambda x: _sds(one_chip, x.shape, x.dtype), t)
    params = place(model.param_shapes())
    store = place(model.cache_shapes(n_store, page))
    decode = jax.jit(kvpool.make_paged_decode(model, pax, sax),
                     donate_argnums=(2,))
    compiled = decode.lower(
        params, _sds(one_chip, (n_slots, 1), jnp.int32), store,
        _sds(one_chip, (n_slots,), jnp.int32),
        _sds(one_chip, (n_slots, pages_per_seq), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES, mem


def test_minitron_paged_kernel_decode_at_the_chat_cell_s_shapes(
        one_chip, monkeypatch):
    """The kernel path of the paged decode at the chat cell's shapes
    (full-width minitron-4b, 24 lanes, s_max 768, 769 pages of 16): the
    paged-attention kernel is in the program, and no dense copy of the
    lanes' extents is (the gather path's temporaries there: 4.83 GB)."""
    monkeypatch.setattr(_ops, "_interpret", lambda: False)   # compile it
    model = build_model(get_config("minitron_4b"))
    n_slots, s_max, page, n_store = 24, 768, 16, 769
    pages_per_seq = s_max // page
    pax, sax = kvpool.page_axes(model)
    place = lambda t: jax.tree.map(                # noqa: E731
        lambda x: _sds(one_chip, x.shape, x.dtype), t)
    decode = jax.jit(kvpool.make_paged_decode(model, pax, sax, pages=True),
                     donate_argnums=(2,))
    compiled = decode.lower(
        place(model.param_shapes()),
        _sds(one_chip, (n_slots, 1), jnp.int32),
        place(model.cache_shapes(n_store, page)),
        _sds(one_chip, (n_slots,), jnp.int32),
        _sds(one_chip, (n_slots, pages_per_seq), jnp.int32)).compile()
    assert _mosaic(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25e9
