"""The paged-attention decode kernel (interpret mode on the CPU) against
the gather path's arithmetic — `kvpool.gather_pages` plus the dense
`sdpa` over ``pos + 1`` positions — and the engine served on the kernel
path: its greedy streams against the gather path's, and a migration
between two kernel-path engines.

The engine picks its path from the store's placement
(`kvpool.decode_path`), which on the CPU is always the gather path; the
engine tests steer it to the kernel path here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_engine as _mk

from repro.configs import get_reduced_config
from repro.kernels import ops
from repro.models import build_model
from repro.models.attention import sdpa
from repro.serving import Request, ServingCluster, kvpool

PS, PPS, N_PAGES, L, D = 4, 6, 40, 2, 16
S_MAX = PS * PPS


def _store(hkv, dtype, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    shape = (L, N_PAGES, PS, hkv, D)
    return {"k": jax.random.normal(ks[0], shape).astype(dtype),
            "v": jax.random.normal(ks[1], shape).astype(dtype)}


def _tables(lengths, order):
    """Page tables for lanes of ``lengths``: each lane's pages through
    its new entry and one page more where the row has room (a reserved
    extent), taken from ``order``, the rest of its row scratch; a lane
    of length 0 is all scratch, as compaction leaves an inactive lane."""
    it = iter(order)
    rows = []
    for n in lengths:
        held = min(-(-(n + 1) // PS) + 1, PPS) if n else 0
        rows.append([next(it) for _ in range(held)]
                    + [kvpool.SCRATCH_PAGE] * (PPS - held))
    return jnp.asarray(rows, jnp.int32)


def _gather_oracle(q, store, k_new, v_new, lengths, tables, layer):
    """The gather path's attention: dense lanes from the page tables,
    the new entry written at ``lengths[b]``, sdpa over ``lengths + 1``."""
    one = {n: leaf[layer][None] for n, leaf in store.items()}
    ax = {"k": 1, "v": 1}
    dense = kvpool.gather_pages(one, tables, ax, {"k": 2, "v": 2})
    b = jnp.arange(q.shape[0])
    k = dense["k"][0].at[b, lengths].set(k_new)
    v = dense["v"][0].at[b, lengths].set(v_new)
    return sdpa(q[:, None], k, v, scale=D ** -0.5, causal=False,
                kv_len=lengths + 1)[:, 0]


LENGTHS = [
    pytest.param((5, 8, 7, 4), id="ragged"),              # %PS: 1, 0, 3, 0
    pytest.param((0, 3, 0, S_MAX - 1), id="inactive+full"),
    pytest.param((PS, 1, 2 * PS - 1, 0), id="one-page"),
]


@pytest.mark.parametrize("lengths", LENGTHS)
@pytest.mark.parametrize("hq, hkv", [(2, 2), (6, 2)], ids=["G1", "G3"])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-6),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("ppb", [1, 4])
def test_kernel_matches_the_gather_path(lengths, hq, hkv, dtype, tol, ppb):
    B = len(lengths)
    store = _store(hkv, dtype, seed=hq + ppb)
    ks = jax.random.split(jax.random.PRNGKey(B * hq), 3)
    q = jax.random.normal(ks[0], (B, hq, D)).astype(dtype)
    k_new = jax.random.normal(ks[1], (B, hkv, D)).astype(dtype)
    v_new = jax.random.normal(ks[2], (B, hkv, D)).astype(dtype)
    pos = jnp.asarray(lengths, jnp.int32)
    order = np.random.default_rng(hq).permutation(np.arange(1, N_PAGES))
    tables = _tables(lengths, order)
    layer = (hq + ppb) % L                     # the cases cover both layers
    out = ops.paged_attention(q, store["k"], store["v"], k_new, v_new, pos,
                              tables, jnp.int32(layer), pages_per_block=ppb)
    want = _gather_oracle(q, store, k_new, v_new, pos, tables, layer)
    assert out.shape == (B, hq, D) and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("hq, hkv", [(2, 2), (6, 2)], ids=["G1", "G3"])
def test_shuffled_pages_read_the_same(hq, hkv):
    """The same KV behind other physical pages gives bitwise the same
    output: the kernel's result depends on the tables' order only."""
    lengths = (9, 0, 23, 4)
    B = len(lengths)
    store = _store(hkv, jnp.float32, seed=3)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, hq, D))
    new = jax.random.normal(jax.random.PRNGKey(2), (2, B, hkv, D))
    pos = jnp.asarray(lengths, jnp.int32)
    in_order = _tables(lengths, range(1, N_PAGES))
    perm = np.random.default_rng(0).permutation(np.arange(1, N_PAGES))
    moved = {n: leaf.at[:, perm].set(leaf[:, 1:]) for n, leaf in
             store.items()}                    # page p now lives at perm[p-1]
    shuffled = _tables(lengths, perm)
    a = ops.paged_attention(q, store["k"], store["v"], new[0], new[1], pos,
                            in_order, jnp.int32(1), pages_per_block=2)
    b = ops.paged_attention(q, moved["k"], moved["v"], new[0], new[1], pos,
                            shuffled, jnp.int32(1), pages_per_block=2)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the decode path, chosen from the model and the store's placement
# ---------------------------------------------------------------------------


class _Dev:
    def __init__(self, platform, i=0):
        self.platform, self.id = platform, i


class _Sharding:
    def __init__(self, *devices):
        self.device_set = set(devices)


@pytest.mark.parametrize("arch, devices, want", [
    ("minitron_4b", [_Dev("tpu")], "pages"),
    ("minitron_4b", [_Dev("tpu", 0), _Dev("tpu", 1)], "dense"),
    ("minitron_4b", [_Dev("cpu")], "dense"),
    ("minicpm3_4b", [_Dev("tpu")], "dense"),           # MLA: no kernel yet
])
def test_decode_path_follows_model_and_placement(arch, devices, want):
    model = build_model(get_reduced_config(arch))
    shardings = {"k": _Sharding(*devices), "v": _Sharding(*devices)}
    assert kvpool.decode_path(model, shardings) == want


# ---------------------------------------------------------------------------
# the engine on the kernel path
# ---------------------------------------------------------------------------


@pytest.fixture
def kernel_path(monkeypatch):
    """Engines built while this is on decode on the kernel path."""
    monkeypatch.setattr(kvpool, "decode_path", lambda model, sh: "pages")


def _prompts(cfg, sizes, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
            for n in sizes]


def _serve(eng, prompts, new):
    reqs = [Request(i, p, max_new_tokens=new) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return {r.rid: list(r.tokens_out) for r in reqs}


@pytest.mark.parametrize("page_size", [4, 8])
def test_kernel_engine_streams_match_the_gather_path(fp32_model,
                                                     monkeypatch, page_size):
    cfg, model, params = fp32_model
    prompts = _prompts(cfg, (5, 9, 6, 13, 3, 8), seed=7)
    gather = _mk(model, params, n_slots=4, s_max=32, page_size=page_size)
    assert gather.kv_path == "dense"
    expect = _serve(gather, prompts, new=8)
    monkeypatch.setattr(kvpool, "decode_path", lambda model, sh: "pages")
    eng = _mk(model, params, n_slots=4, s_max=32, page_size=page_size)
    assert eng.kv_path == "pages"
    assert _serve(eng, prompts, new=8) == expect
    assert eng.pool.free_pages == eng.pool.n_pages


def test_kernel_engine_migration_bitwise(fp32_model, kernel_path):
    """A decoding cohort moved between two kernel-path engines onto
    other physical pages finishes with the unmigrated run's streams."""
    cfg, model, params = fp32_model
    prompts = _prompts(cfg, (5, 7, 6, 8), seed=5)
    expect = _serve(_mk(model, params, n_slots=4, page_size=8), prompts,
                    new=8)
    cluster = ServingCluster()
    cluster.register("src", _mk(model, params, n_slots=4, page_size=8))
    reqs = [Request(i, p, max_new_tokens=8) for i, p in enumerate(prompts)]
    for r in reqs:
        cluster.submit(r)
    for _ in range(3):
        cluster.step()
    dst = _mk(model, params, n_slots=4, page_size=8)
    dst.pool.alloc(3)                 # the cohort lands on other pages
    cluster.register("dst", dst)
    records = cluster.migrate_requests("src", "dst")
    assert len(records) == 4 and dst.kv_path == "pages"
    cluster.run()
    assert {r.rid: list(r.tokens_out) for r in reqs} == expect


@pytest.mark.parametrize("before, after", [("pages", "dense"),
                                           ("dense", "pages")])
def test_swap_plan_follows_the_store_to_its_path(fp32_model, monkeypatch,
                                                 before, after):
    """A swap that moves the store re-reads the path (as a one-chip store
    moved onto several chips leaves the kernel path): the JIT decode is
    rebuilt for it, and the streams in flight finish as on the gather
    path."""
    cfg, model, params = fp32_model
    prompts = _prompts(cfg, (5, 9, 6), seed=13)
    expect = _serve(_mk(model, params, n_slots=4, page_size=8), prompts,
                    new=8)
    monkeypatch.setattr(kvpool, "decode_path", lambda model, sh: before)
    eng = _mk(model, params, n_slots=4, page_size=8)
    reqs = [Request(i, p, max_new_tokens=8) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    monkeypatch.setattr(kvpool, "decode_path", lambda model, sh: after)
    eng.pause()
    same = lambda t: jax.tree.map(lambda x: x.sharding, t)   # noqa: E731
    eng.swap_plan(shardings={"params": same(eng.params),
                             "cache": same(eng.cache)})
    eng.resume()
    assert eng.kv_path == after
    eng.run()
    assert {r.rid: list(r.tokens_out) for r in reqs} == expect
