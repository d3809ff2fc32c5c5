#!/usr/bin/env python3
"""Smoke test of the served path on a TPU.

Serves minitron-4b at its published widths (random bf16 weights made from
``--seed``, nothing downloaded) through the normal entry points:
``Orchestrator.submit(..., apply_to=cluster)`` -> `ServingCluster` ->
paged `ServingEngine` -> AOT prefill and decode. Then it runs the three
Pallas kernels compiled for the chip against their jnp references.

    python3 chip_smoke.py                # phases A and B, one chip
    python3 chip_smoke.py --chips 4      # the four-chip path only
    JAX_PLATFORMS=cpu python3 chip_smoke.py --reduced      # rehearsal
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 chip_smoke.py --reduced --chips 4          # rehearsal

The last line of stdout is ``{"ok": true, "device": {...}}`` only when
every phase passed and JAX runs on a TPU; in every other case the script
exits non-zero and prints no such line. A rehearsal (``--reduced``) runs
the phases at reduced widths on any backend and still exits non-zero off
the TPU. Everything runs in this one process: it holds the chip, so it
starts no child that needs it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import threading
import time
import traceback
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, get_reduced_config  # noqa: E402
from repro.core import Orchestrator  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import (  # noqa: E402
    Request,
    RoutingError,
    ServingCluster,
    ServingEngine,
)
from repro.serving.migration import fit_single  # noqa: E402
from repro.serving.prepare import SWAPPED  # noqa: E402
from repro.sharding import ShardingPlan, default_plan  # noqa: E402
from repro.sharding.plan import plan_satisfies, plan_to_shardings  # noqa: E402

ARCH = "minitron_4b"
PHI_INTENT = "Phi traffic must remain inside the pod."
LABELS = ({"data-type": "phi"}, {"data-type": "general"})
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# bf16 tolerance (atol = rtol) shared with tests/test_kernels.py
BF16_TOL = 2e-2
# first-token logits of one prompt served on two layouts (one chip vs
# two-chip tensor parallel) may differ by the bf16 rounding of a
# different reduction order; the largest difference must stay under
# this fraction of the largest logit
LAYOUT_LOGIT_TOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Engine and traffic shape of one run."""

    n_slots: int = 8
    s_max: int = 512
    page_size: int = 16
    n_requests: int = 16
    prompt_lo: int = 32
    prompt_hi: int = 400
    max_new: int = 32


FULL = Sizes()
REDUCED = Sizes(s_max=128, n_requests=8, prompt_lo=8, prompt_hi=100,
                max_new=8)
# the four-chip path serves one prompt length: each of its four engine
# layouts then compiles decode + one prefill, not a bucket ladder
MULTI = dataclasses.replace(FULL, prompt_lo=128, prompt_hi=128)
MULTI_REDUCED = dataclasses.replace(REDUCED, prompt_lo=32, prompt_hi=32)


class CompileCounter:
    """Counts backend compiles (persistent-cache loads included) that
    happen on the serving thread inside named windows. Compiles on other
    threads — the background PREPARE worker — are not serving-path
    compiles and are not counted."""

    def __init__(self):
        self.thread = threading.current_thread()
        self.window: Optional[str] = None
        self.counts: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if (event == BACKEND_COMPILE_EVENT and self.window is not None
                and threading.current_thread() is self.thread):
            self.counts[self.window] += 1

    @contextlib.contextmanager
    def count(self, name: str):
        self.counts.setdefault(name, 0)
        self.window = name
        try:
            yield
        finally:
            self.window = None

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def device_info() -> Dict[str, object]:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def init_params(model, seed: int, shardings=None):
    """Random weights straight to their dtype on the device: under jit no
    float32 transient of a whole weight is ever materialized."""
    init = jax.jit(model.init_params, out_shardings=shardings)
    params = init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params


def make_wave(cluster, rng, vocab: int, sizes: Sizes, base: int,
              n: int, labels=LABELS) -> List[Request]:
    """Submit ``n`` seeded requests through the router, cycling through
    ``labels``."""
    reqs = []
    for i in range(n):
        S = int(rng.integers(sizes.prompt_lo, sizes.prompt_hi + 1))
        prompt = rng.integers(2, vocab, size=S).astype(np.int32)
        req = Request(base + i, prompt, max_new_tokens=sizes.max_new,
                      labels=dict(labels[i % len(labels)]))
        cluster.submit(req)
        reqs.append(req)
    return reqs


def clone(req: Request, rid: int) -> Request:
    return Request(rid, req.prompt.copy(), max_new_tokens=req.max_new_tokens,
                   labels=dict(req.labels))


def check_tokens(reqs: List[Request], vocab: int) -> None:
    for r in reqs:
        assert len(r.tokens_out) == r.max_new_tokens, (
            f"request {r.rid} finished with {len(r.tokens_out)} of "
            f"{r.max_new_tokens} tokens")
        assert all(0 <= t < vocab for t in r.tokens_out), (
            f"request {r.rid} produced a token outside [0, {vocab})")


def first_logits(model, params, prompt: np.ndarray, s_max: int):
    """Prefill and one decode step of the plain model (no engine): the
    logits the served path samples from, for finiteness checks."""
    logits, cache = jax.jit(model.prefill)(params, {"tokens": prompt[None]})
    cache = fit_single(cache, model.cache_shapes(1, s_max))
    tok = jnp.argmax(logits[:, : model.cfg.vocab_size], axis=-1)
    dec, _ = jax.jit(model.decode_step)(
        params, tok[:, None].astype(jnp.int32), cache,
        jnp.asarray([len(prompt)], jnp.int32))
    return np.asarray(logits, np.float32), np.asarray(dec, np.float32)


def check_logits(model, params, reqs: List[Request], s_max: int,
                 log: Callable) -> None:
    matches = 0
    for r in reqs:
        pre, dec = first_logits(model, params, r.prompt, s_max)
        assert np.isfinite(pre).all() and np.isfinite(dec).all(), (
            f"non-finite logits for request {r.rid}")
        matches += int(np.argmax(pre[0, : model.cfg.vocab_size])
                       == r.tokens_out[0])
    log(f"  logits finite for {len(reqs)} prompts; served first token = "
        f"plain-prefill argmax for {matches}/{len(reqs)} (observation)")


# ---------------------------------------------------------------------------
# phase A: serve, reconfigure, migrate (one chip)
# ---------------------------------------------------------------------------


def phase_a(model, params, sizes: Sizes, *, seed: int,
            log: Callable = print) -> Dict[str, object]:
    """Serve through the cluster, apply the phi intent, migrate.

    Asserts: every token in ``[0, vocab)`` and finite logits; every
    reconfigure ticket SWAPPED; migrated streams bitwise equal to an
    uninterrupted run of the same requests on the same layout; no compile
    on the serving thread once PREPARE has finished.
    """
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(seed)
    counter = CompileCounter()
    try:
        cluster = ServingCluster()
        e0 = ServingEngine(model, params, n_slots=sizes.n_slots,
                           s_max=sizes.s_max, page_size=sizes.page_size)
        # PREPARE before any request: decode + the prefill bucket ladder
        t0 = time.perf_counter()
        with counter.count("prepare"):
            spawn0 = cluster.spawn_engine("edge0", e0, prefill_buckets=True)
        log(f"  prepare edge0: {time.perf_counter() - t0:.1f}s "
            f"(aot x{spawn0.compiled_in_prepare})")
        # the listener must see PREPARE's compiles, or a zero count below
        # would prove nothing
        assert counter.counts.pop("prepare") > 0, "compile listener is deaf"

        with counter.count("wave1"):
            w1 = make_wave(cluster, rng, vocab, sizes, 0, sizes.n_requests)
            cluster.run()
        check_tokens(w1, vocab)
        log(f"  wave1: {len(w1)} requests served")
        check_logits(model, e0.params, w1[:1], sizes.s_max, log)

        # the intent's PREPARE compiles on the background worker while
        # general traffic keeps flowing; phi requests are refused
        # (fail-closed) until the swap commits, so they come after it
        orch = Orchestrator()
        with counter.count("wave2"):
            res = orch.submit(PHI_INTENT, apply_to=cluster,
                              async_reconfig=True)
            w2 = make_wave(cluster, rng, vocab, sizes, 1000,
                           sizes.n_requests // 2, labels=LABELS[1:])
            cluster.run(wait_pending=True)
            w2 += make_wave(cluster, rng, vocab, sizes, 1500,
                            sizes.n_requests // 2)
            cluster.run()
        assert res.success, res.report.summary()
        states = {name: t.state for name, t in res.reports.items()}
        log(f"  intent: {res.report.summary()}; tickets {states}")
        assert states and all(s == SWAPPED for s in states.values()), states
        for t in res.reports.values():
            log(f"  {t.result().summary()}")
        check_tokens(w2, vocab)
        assert plan_satisfies(e0.plan, cluster.route_constraints()["phi"])
        log(f"  wave2: {len(w2)} requests served across the swap")

        # a second engine on the SAME weight arrays, then migrate into it
        e1 = ServingEngine(model, e0.params, n_slots=sizes.n_slots,
                           s_max=sizes.s_max, page_size=sizes.page_size)
        t0 = time.perf_counter()
        spawn1 = cluster.spawn_engine("edge1", e1, plan=e0.plan,
                                      prefill_buckets=True)
        log(f"  prepare edge1: {time.perf_counter() - t0:.1f}s "
            f"(aot x{spawn1.compiled_in_prepare})")
        shared = all(a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()
                     for a, b in zip(jax.tree.leaves(e0.params),
                                     jax.tree.leaves(e1.params)))
        assert shared, "edge1 holds a second copy of the weights"
        for eng in (e0, e1):           # migration's PREPARE-equivalent
            eng.warm_migration()

        with counter.count("wave3+migrate"):
            w3 = make_wave(cluster, rng, vocab, sizes, 2000, sizes.n_slots)
            for _ in range(3):
                cluster.step()
            retire = cluster.retire_engine("edge0", mode="migrate")
            cluster.run()
            ref = [clone(r, r.rid + 10_000) for r in w3]
            for r in ref:
                cluster.submit(r)
            cluster.run()
        check_tokens(w3, vocab)
        moved = [m for m in retire.migrations if m.phase == "decoding"]
        log(f"  {retire.summary()}")
        log("  migration pauses (observation): "
            + ", ".join(f"rid {m.rid}: {m.pause_s * 1e3:.1f}ms"
                        for m in retire.migrations))
        assert moved, "no in-flight request was migrated"
        mismatched = [r.rid for r, c in zip(w3, ref)
                      if r.tokens_out != c.tokens_out]
        assert not mismatched, (
            f"migrated streams differ from the uninterrupted run: "
            f"{mismatched}")
        log(f"  {len(moved)} migrated streams bitwise equal to the "
            "uninterrupted run")
        log(f"  serving-thread compiles after PREPARE: {counter.counts}")
        assert not any(counter.counts.values()), counter.counts
        return {"compiles": dict(counter.counts), "migrated": len(moved),
                "downtime_s": [t.report.downtime_s
                               for t in res.reports.values()]}
    finally:
        counter.close()


# ---------------------------------------------------------------------------
# phase B: the Pallas kernels, compiled
# ---------------------------------------------------------------------------


def phase_b(*, reduced: bool, log: Callable = print) -> Dict[str, object]:
    """flash_attention, moe_topk and ssd_scan against their jnp
    references at bf16 tolerances; on a TPU each must lower to a Mosaic
    custom call (never the interpreter)."""
    from repro.kernels import ops
    from repro.kernels.ref import moe_topk_ref
    from repro.models.attention import sdpa
    from repro.models.ssm import ssd_scan_ref

    on_tpu = jax.default_backend() == "tpu"
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    out: Dict[str, object] = {}

    def compiled_kernel(fn, *args, **kw) -> bool:
        text = fn.lower(*args, **kw).compile().as_text()
        return "tpu_custom_call" in text

    def close(name, got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        np.testing.assert_allclose(got, want, atol=BF16_TOL, rtol=BF16_TOL,
                                   err_msg=name)
        return err

    # flash attention at minitron widths (GQA 24/8, head 128)
    Hq, Hkv, D = (4, 2, 32) if reduced else (24, 8, 128)
    for S in ((64, 37) if reduced else (512, 37)):
        q = jax.random.normal(ks[0], (1, S, Hq, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, S, Hkv, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, S, Hkv, D), jnp.bfloat16)
        kw = {"q_block": 32, "k_block": 32} if reduced else {}
        got = ops.flash_attention(q, k, v, causal=True, **kw)
        with jax.default_matmul_precision("highest"):
            want = sdpa(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), scale=D ** -0.5, causal=True)
        err = close(f"flash S={S}", got, want)
        mosaic = compiled_kernel(ops.flash_attention, q, k, v, causal=True,
                                 **kw)
        out[f"flash_{S}"] = {"max_abs_err": err, "mosaic": mosaic}

    # moe_topk at qwen2-moe widths (512 tokens x 60 experts, k=4)
    T, E, k_top = (64, 16, 4) if reduced else (512, 60, 4)
    logits = jax.random.normal(ks[3], (T, E), jnp.float32)
    w, idx = ops.moe_topk(logits, k_top)
    w_ref, idx_ref = moe_topk_ref(logits, k_top)
    err = close("moe_topk weights", w, w_ref)
    # an index may differ only where two reference weights tie
    gaps = np.abs(np.diff(np.asarray(w_ref), axis=-1)).min(axis=-1)
    differ = (np.asarray(idx) != np.asarray(idx_ref)).any(axis=-1)
    assert not (differ & (gaps > 1e-6)).any(), "moe_topk indices differ"
    out["moe_topk"] = {"max_abs_err": err, "index_rows_differ":
                       int(differ.sum()),
                       "mosaic": compiled_kernel(ops.moe_topk, logits,
                                                 k_top)}

    # ssd_scan at mamba2-370m widths (32 heads x 64, state 128, chunk 256)
    S, H, P, G, N, chunk = ((64, 4, 16, 1, 32, 32) if reduced
                            else (512, 32, 64, 1, 128, 256))
    x = jax.random.normal(ks[4], (1, S, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[5], (1, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[6], (H,)) * 0.5)
    Bm, Cm = jax.random.normal(ks[7], (2, 1, S, G, N), jnp.bfloat16) * 0.3
    y, h = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    with jax.default_matmul_precision("highest"):
        y_ref, h_ref = ssd_scan_ref(x.astype(jnp.float32), dt, A,
                                    Bm.astype(jnp.float32),
                                    Cm.astype(jnp.float32), chunk=chunk)
    err = max(close("ssd y", y, y_ref), close("ssd state", h, h_ref))
    out["ssd_scan"] = {"max_abs_err": err,
                       "mosaic": compiled_kernel(ops.ssd_scan, x, dt, A, Bm,
                                                 Cm, chunk=chunk)}
    for name, r in out.items():
        log(f"  {name}: {r}")
        if on_tpu:
            assert r["mosaic"], f"{name} did not compile to a Mosaic kernel"
    return out


# ---------------------------------------------------------------------------
# the four-chip path (--chips 4)
# ---------------------------------------------------------------------------


def inflight_rids(engine: ServingEngine) -> List[int]:
    return [r.rid for r in engine.slot_req if r is not None]


def pinned_plan(pod: int) -> ShardingPlan:
    return default_plan(multi_pod=True).with_(
        device_constraints=(("pod", pod),), forbidden_collective_axes=("pod",))


def phase_multichip(model, sizes: Sizes, *, seed: int,
                    log: Callable = print) -> Dict[str, object]:
    """An engine over all four chips moves to two under the pod intent
    while requests are in flight; in-flight requests then migrate to an
    engine pinned to the other pod; `verify_engine_hlo` reads the TPU
    HLO of both layouts. Compared with the same requests on one chip."""
    devices = jax.devices()
    assert len(devices) >= 4, f"needs four devices, have {len(devices)}"
    vocab = model.cfg.vocab_size
    wave_rng = np.random.default_rng(seed)
    # watermark=1 keeps the page store (n_pages + scratch) divisible by
    # the pod axis, so the unrestricted plan really shards it across pods
    eng_kw = dict(n_slots=sizes.n_slots, s_max=sizes.s_max,
                  page_size=sizes.page_size, watermark=1)

    # --- the same requests on the one-chip layout (reference) ---
    one = ServingCluster()
    params = init_params(model, seed)
    ref_engine = ServingEngine(model, params, **eng_kw)
    lengths = (sizes.prompt_lo,)
    one.spawn_engine("one", ref_engine, prefill_lengths=lengths)
    prompts = make_wave(one, wave_rng, vocab, sizes, 0, sizes.n_slots)
    one.run()
    one_logits = first_logits(model, params, prompts[0].prompt,
                              sizes.s_max)[0]
    del one, ref_engine, params
    gc.collect()                       # free the one-chip weights

    # --- four chips: (pod, data, model) = (2, 1, 2) ---
    mesh = jax.sharding.Mesh(np.asarray(devices[:4]).reshape(2, 1, 2),
                             ("pod", "data", "model"))
    cluster = ServingCluster(mesh=mesh)
    wide_plan = default_plan(multi_pod=True)
    # weights made straight onto the four-chip layout
    wide = ServingEngine(model, init_params(model, seed, plan_to_shardings(
        model.cfg, wide_plan, mesh, n_slots=1)["params"]), **eng_kw)
    t0 = time.perf_counter()
    cluster.spawn_engine("wide", wide, plan=wide_plan,
                         prefill_lengths=lengths)
    log(f"  prepare wide (4 chips): {time.perf_counter() - t0:.1f}s; "
        f"params on {len(wide.params['embed'].sharding.device_set)} chips")
    unrestricted_hlo = wide.decode_hlo_text()

    reqs = [clone(r, r.rid) for r in prompts]
    for r in reqs:
        cluster.submit(r)
    for _ in range(2):
        cluster.step()
    # the swap commits while these requests sit resident in their lanes
    res = Orchestrator().submit(PHI_INTENT, apply_to=cluster)
    assert res.success and "wide" in res.reports, res.reports
    n_after = len(wide.params["embed"].sharding.device_set)
    log(f"  intent swap with {len(inflight_rids(wide))} requests in flight: "
        f"{res.reports['wide'].summary()}; 4 -> {n_after} chips, "
        f"plan {wide.plan.device_constraints}")
    assert n_after == 2, n_after

    pod1 = ServingEngine(model, wide.params, **eng_kw)
    cluster.spawn_engine("pod1", pod1, plan=pinned_plan(1),
                         prefill_lengths=lengths)
    assert {d.id for d in pod1.params["embed"].sharding.device_set} \
        .isdisjoint({d.id for d in wide.params["embed"].sharding.device_set})
    inflight = {r.rid: r for r in wide.slot_req if r is not None}
    general = [rid for rid, r in inflight.items()
               if r.labels["data-type"] == "general"]
    phi = [rid for rid, r in inflight.items()
           if r.labels["data-type"] == "phi"]
    if phi:
        try:
            cluster.migrate_requests("wide", "pod1", rids=phi[:1])
        except RoutingError:
            log("  phi request refused by the pod-1 engine (fail-closed)")
        else:
            raise AssertionError("phi request migrated out of pod 0")
    assert general, "no general request in flight to migrate"
    records = cluster.migrate_requests("wide", "pod1", rids=general)
    log("  cross-pod migration: " + ", ".join(
        f"rid {m.rid} {m.bytes_moved / 2**20:.1f}MiB "
        f"{m.pause_s * 1e3:.1f}ms" for m in records))
    cluster.run()
    check_tokens(reqs, vocab)

    # bitwise: migrated vs unmigrated on the same (two-chip) layout
    again = [clone(r, r.rid + 10_000) for r in reqs if r.rid in general]
    for r in again:
        cluster.submit(r)
    cluster.run()
    by_rid = {r.rid: r for r in reqs}
    bad = [r.rid for r in again
           if r.tokens_out != by_rid[r.rid - 10_000].tokens_out]
    assert not bad, f"migrated streams differ from unmigrated: {bad}"
    log(f"  {len(general)} migrated streams bitwise equal to unmigrated")

    # compiled-HLO validation of the TPU executables
    accept = cluster.verify_engine_hlo("wide")
    assert accept is not None
    log(f"  verify pinned engine: accepted ({accept})")
    try:
        cluster.verify_engine_hlo("wide", hlo_text=unrestricted_hlo)
    except ValueError as err:
        log(f"  verify unrestricted engine: rejected ({err})")
    else:
        raise AssertionError("unrestricted HLO passed the pod constraint")

    # across layouts: one chip vs two-chip tensor parallel
    got = first_logits(model, wide.params, prompts[0].prompt,
                       sizes.s_max)[0]
    diff = float(np.max(np.abs(got - one_logits))
                 / np.max(np.abs(one_logits)))
    same = sum(a.tokens_out == b.tokens_out for a, b in zip(reqs, prompts))
    log(f"  one chip vs two-chip TP: first-token logit diff {diff:.4g} of "
        f"max |logit| (tolerance {LAYOUT_LOGIT_TOL}); {same}/{len(reqs)} "
        "streams identical (observation)")
    assert diff <= LAYOUT_LOGIT_TOL, diff
    return {"chips_before": 4, "chips_after": n_after,
            "migrated": len(records), "layout_logit_diff": diff}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def verdict(results: Dict[str, bool], device: Dict[str, object],
            chips: int) -> Dict[str, object]:
    """The run's verdict: ok only if every phase passed on ``chips``
    TPU devices."""
    ok = (bool(results) and all(results.values())
          and device["platform"] == "tpu" and device["count"] >= chips)
    return {"ok": ok, "device": device}


def run_phase(name: str, fn: Callable, results: Dict[str, bool],
              log: Callable) -> None:
    log(f"phase {name}:")
    t0 = time.perf_counter()
    try:
        fn()
    except Exception:              # noqa: BLE001 - reported, run fails
        results[name] = False
        log(traceback.format_exc())
        log(f"phase {name}: FAILED after {time.perf_counter() - t0:.1f}s")
    else:
        results[name] = True
        log(f"phase {name}: passed in {time.perf_counter() - t0:.1f}s; "
            f"peak_bytes_in_use={peak_bytes()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="rehearse at reduced widths on any backend")
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(msg, flush=True)

    cache_dir = configure_compile_cache()
    device = device_info()
    log(f"jax {jax.__version__}; devices {device}; compile cache {cache_dir}")
    if device["platform"] != "tpu" and not args.reduced:
        log("FAIL: no TPU found (JAX runs on "
            f"{device['platform']}); nothing was run")
        return 1

    cfg = get_reduced_config(ARCH) if args.reduced else get_config(ARCH)
    sizes = REDUCED if args.reduced else FULL
    model = build_model(cfg)
    log(f"model {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, {cfg.param_dtype}; sizes {sizes}")
    results: Dict[str, bool] = {}
    if args.chips == 4:
        multi = MULTI_REDUCED if args.reduced else MULTI
        run_phase("4chip", lambda: phase_multichip(model, multi,
                                                   seed=args.seed, log=log),
                  results, log)
    else:
        t0 = time.perf_counter()
        params = init_params(model, args.seed)
        n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
        log(f"params: {n_bytes / 1e9:.2f} GB initialised under jit in "
            f"{time.perf_counter() - t0:.1f}s")
        run_phase("A", lambda: phase_a(model, params, sizes, seed=args.seed,
                                       log=log), results, log)
        del params
        run_phase("B", lambda: phase_b(reduced=args.reduced, log=log),
                  results, log)

    v = verdict(results, device, args.chips)
    if not v["ok"]:
        log(f"FAIL: phases {results} on {device}")
        return 1
    print(json.dumps(v), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
