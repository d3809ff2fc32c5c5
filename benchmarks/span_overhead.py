"""Cost of the serving step's profiler spans with no profiler session.

    PYTHONPATH=src python benchmarks/span_overhead.py

A tiny paged engine (reduced minitron-4b, float32, 24 lanes) serves a
stream that keeps every lane busy and admits a few requests a step. Two
readings of what the spans (`repro.obs.trace.annotate`) cost per
``cluster.step``:

  * attributed: the spans a step opens (counted) times the cost of one
    span, timed in a tight loop with and without args;
  * end to end: the step's host time with the spans, less its time with
    ``annotate`` swapped for a no-op, alternating step by step (medians).

Prints one JSON object. Host-clock numbers of this CPU only.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import statistics
import time
import timeit

import jax
import numpy as np

from repro.configs import get_reduced_config
from repro.models import build_model
from repro.obs import trace as obs_trace
from repro.serving import Request, ServingCluster, ServingEngine
from repro.serving import cluster as cluster_mod
from repro.serving import engine as engine_mod

LANES = 24
STEPS = 40
ROUNDS = 16


def build():
    cfg = dataclasses.replace(get_reduced_config("minitron_4b"),
                              param_dtype="float32", activ_dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    cluster = ServingCluster()
    eng = ServingEngine(model, params, n_slots=LANES, s_max=64)
    cluster.spawn_engine("e0", eng, prefill_buckets=True)
    return cfg, cluster, eng


def serve(cluster, eng, cfg, rng, steps):
    """Host seconds of each of ``steps`` steps, the queue kept full."""
    out = []
    for _ in range(steps):
        while eng.load < LANES + 2:
            n = int(rng.integers(4, 30))
            cluster.submit(Request(0, rng.integers(2, cfg.vocab_size, n)
                                   .astype(np.int32),
                                   max_new_tokens=int(rng.integers(4, 24))))
        t = time.perf_counter()
        cluster.step()
        out.append(time.perf_counter() - t)
    return out


def main() -> dict:
    cfg, cluster, eng = build()
    rng = np.random.default_rng(0)
    serve(cluster, eng, cfg, rng, 20)                  # warm every shape

    noop = contextlib.nullcontext()
    real = obs_trace.annotate
    calls = [0]

    def counted(name, **args):
        calls[0] += 1
        return real(name, **args)

    def use(fn):
        engine_mod.annotate = cluster_mod.annotate = fn

    use(counted)
    serve(cluster, eng, cfg, rng, STEPS)
    spans_per_step = calls[0] / STEPS

    on, off = [], []
    try:
        for _ in range(ROUNDS * STEPS):         # alternate step by step
            use(real)
            on += serve(cluster, eng, cfg, rng, 1)
            use(lambda name, **args: noop)
            off += serve(cluster, eng, cfg, rng, 1)
    finally:
        use(real)

    def per_span(stmt):
        n = 200_000
        return min(timeit.repeat(stmt, globals={"annotate": real},
                                 number=n, repeat=5)) / n

    bare = per_span("with annotate('engine.step'): pass")
    with_args = per_span(
        "with annotate('engine.prefill', prompt_len=70, bucket=128): pass")
    return {
        "lanes": LANES, "spans_per_step": spans_per_step,
        "span_us": bare * 1e6, "span_with_args_us": with_args * 1e6,
        "attributed_us_per_step": spans_per_step * with_args * 1e6,
        "step_ms_on": statistics.median(on) * 1e3,
        "step_ms_off": statistics.median(off) * 1e3,
        "end_to_end_us_per_step":
            (statistics.median(on) - statistics.median(off)) * 1e6,
    }


if __name__ == "__main__":
    print(json.dumps(main()))
