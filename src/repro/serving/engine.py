"""Continuous-batching serving engine with per-request TTFT/TPOT metrics
and an explicit reconfiguration lifecycle.

Slot-based decode batching: a fixed (B, S_max) KV pool; requests prefill
into a free slot and decode step-locked with the rest of the batch (the
standard TPU serving shape — static shapes, no re-compilation per request).

Privacy intents attach *labels* to requests (e.g. data-type=phi); the
`ServingCluster` (repro.serving.cluster) maps labeled requests to engines
whose `ShardingPlan` carries the matching device constraints, and the
validator checks the engine's compiled HLO against the routing constraints.

Lifecycle (the public swap protocol — no private-attribute mutation):

    engine.pause()                    # stop stepping; submissions still queue
    engine.drain()                    # block until in-flight device work done
    engine.swap_plan(plan,            # migrate params/cache, install
                     shardings=...,   #   AOT executables compiled ahead of
                     executables=...) #   time (the swap window never compiles)
    engine.resume()

AOT executables come from `aot_executables()`: decode is fully static
(n_slots, 1) so one executable covers it; prefill is compiled per prompt
length (the engine records lengths it has seen so a reconfiguration can
pre-compile exactly the live traffic shapes).
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.models import Model
from repro.obs import events as obs_events
from repro.obs.trace import annotate
from repro.serving import kvpool, migration
from repro.serving.migration import MigrationError, SlotSnapshot
from repro.sharding.plan import ShardingPlan, default_plan

PyTree = Any

METRIC_KEYS = ("completed", "ttft_mean_s", "ttft_p99_s",
               "tpot_mean_s", "tpot_p99_s")


class EngineStateError(RuntimeError):
    """Raised when a lifecycle method is called in the wrong state."""


@dataclasses.dataclass
class Request:
    """One generation request flowing through an engine.

    Attributes:
        rid: caller-chosen request id (metrics/bookkeeping only).
        prompt: ``(S_prompt,)`` int32 token ids.
        max_new_tokens: decode budget; generation also stops at the KV
            pool's sequence capacity.
        labels: tenancy labels (e.g. ``{"data-type": "phi"}``) — the
            cluster routes and aggregates on these.
        t_submit / t_admit / t_first / t_done: serving-clock stamps set
            by the engine at submission, when admission takes the
            request off the queue, at the first token, and at completion.
        tokens_out: generated token ids (first entry comes from prefill).
    """

    rid: int
    prompt: np.ndarray                 # (S_prompt,) int32
    max_new_tokens: int = 16
    labels: Dict[str, str] = dataclasses.field(default_factory=dict)
    # metrics
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    tokens_out: List[int] = dataclasses.field(default_factory=list)

    @property
    def ttft(self) -> float:
        """Time to first token (seconds): first-token stamp - submit."""
        return self.t_first - self.t_submit

    @property
    def tpot(self) -> float:
        """Mean time per output token (seconds) over the decode phase."""
        n = max(len(self.tokens_out) - 1, 1)
        return (self.t_done - self.t_first) / n


def compute_metrics(done: Sequence[Request]) -> Dict[str, float]:
    """TTFT/TPOT summary over a set of completed requests.

    Args:
        done: completed requests (``t_done`` set); any iterable window.

    Returns:
        Always the full `METRIC_KEYS` set — ``completed`` plus mean/p99
        TTFT and TPOT, with NaN for undefined statistics — so callers can
        index unconditionally (an empty window is a value, not a missing
        key).
    """
    out: Dict[str, float] = {
        "completed": len(done),
        "ttft_mean_s": math.nan, "ttft_p99_s": math.nan,
        "tpot_mean_s": math.nan, "tpot_p99_s": math.nan,
    }
    if done:
        ttfts = [r.ttft for r in done]
        tpots = [r.tpot for r in done]
        out.update(
            ttft_mean_s=float(np.mean(ttfts)),
            ttft_p99_s=float(np.percentile(ttfts, 99)),
            tpot_mean_s=float(np.mean(tpots)),
            tpot_p99_s=float(np.percentile(tpots, 99)),
        )
    return out


class ServingEngine:
    """Single-model engine; decode batch of `n_slots` sequences.

    Two KV memory layouts (see `repro.serving.kvpool`):

      * **paged** (default for attn/MLA models): KV lives in a
        `PagedKVPool` of fixed-size pages; admission is token-granular
        (a request reserves ``ceil(need / page_size)`` pages for its
        worst-case extent and frees them on retirement, failing CLOSED
        when the pool is out of pages) and active requests are packed
        into the decode batch each step — a request owns pages, not a
        lane, so ``n_slots`` is purely the decode width.
      * **slot-granular** (SSM/enc-dec models, or ``paged=False``): the
        original fixed ``(n_slots, s_max)`` pool; a request pins one
        slot for its lifetime.

    Token streams are bitwise identical between the two layouts (decode
    masks every position beyond the write cursor before the softmax, so
    page-granule garbage can never leak into a logit).

    Args:
        model: the `repro.models.Model` to serve.
        params: its parameter pytree (device arrays).
        n_slots: continuous-batching width (decode batch dim).
        s_max: KV sequence capacity per request.
        greedy: greedy sampling (the only mode currently implemented).
        plan: initial `ShardingPlan`; `default_plan()` when omitted.
        labels: tenancy labels. Under cluster routing an engine label
            only EXCLUDES requests that carry a contradicting value: an
            engine labeled ``{"data-type": "phi"}`` never receives
            ``data-type=general`` traffic, but requests without the label
            can still land on it. An unlabeled engine serves all.
        paged: force the paged pool on/off; ``None`` auto-selects
            (paged wherever `kvpool.supports_paging` holds).
        page_size: tokens per KV page (paged mode; clamped to
            ``s_max``).
        kv_tokens: token capacity of the paged pool (admission budget).
            Defaults to ``n_slots * ceil(s_max/page_size) * page_size``
            — the slot-granular pool's capacity in page units — so the
            default paged engine never admits less than the slot engine
            would. Benchmarks decouple it from ``n_slots`` to trade
            decode width against memory.
        watermark: free pages admissions must leave behind (headroom
            for migration imports, which may spend it); allocated ON TOP
            of ``kv_tokens``, so the admission budget is unaffected.
        role: serving role under disaggregated prefill/decode placement:
            ``"unified"`` (default — serves a request end to end),
            ``"prefill"`` (receives new requests; the cluster hands each
            one off to a decode engine at its first-token boundary) or
            ``"decode"`` (never routed new requests; receives in-flight
            work via migration). The engine itself serves identically in
            every role — the role only steers cluster routing/handoff.
    """

    ROLES = ("unified", "prefill", "decode")

    # cap on the prompt-length fallback set `aot_executables` compiles for:
    # a long-lived engine sees unboundedly many distinct lengths, but only
    # the most recent ones predict live traffic
    MAX_AOT_PREFILL = 8
    # smallest padded-prefill bucket (powers of two up to s_max are
    # compiled when `aot_executables(..., prefill_buckets=True)`)
    BUCKET_MIN = 8

    def __init__(self, model: Model, params: PyTree, *, n_slots: int = 4,
                 s_max: int = 128, greedy: bool = True,
                 plan: Optional[ShardingPlan] = None,
                 labels: Optional[Dict[str, str]] = None,
                 paged: Optional[bool] = None, page_size: int = 16,
                 kv_tokens: Optional[int] = None, watermark: int = 0,
                 role: str = "unified"):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.s_max = s_max
        self.greedy = greedy
        self.vocab = model.cfg.vocab_size
        self.plan = plan or default_plan()
        self.labels = dict(labels or {})
        self.role = role
        # display name for flight-recorder events/spans; the cluster
        # sets it to the registered engine name
        self.obs_name = ""

        self.paged = (kvpool.supports_paging(model) if paged is None
                      else bool(paged))
        if self.paged and paged and not kvpool.supports_paging(model):
            raise ValueError("model has non-positional cache state "
                             "(SSM/enc-dec) — it cannot be paged")
        if self.paged:
            self.page_size = min(page_size, s_max)
            self.pages_per_seq = -(-s_max // self.page_size)
            if kv_tokens is None:
                kv_tokens = n_slots * self.pages_per_seq * self.page_size
            self.pool: Optional[kvpool.PagedKVPool] = kvpool.PagedKVPool(
                self.page_size,
                -(-kv_tokens // self.page_size) + watermark,
                watermark=watermark)
            self._pax, self._sax = kvpool.page_axes(model)
            self.cache = self.pool.init_store(model)
            # per-lane page tables (scratch-padded to pages_per_seq) and
            # the owned-page lists the allocator accounting tracks
            self.page_tables = np.full((n_slots, self.pages_per_seq),
                                       kvpool.SCRATCH_PAGE, dtype=np.int32)
            self.slot_pages: List[List[int]] = [[] for _ in range(n_slots)]
            # device-side mirror of page_tables, re-uploaded only when
            # the host copy changes (tables are stable across pure-decode
            # steps, so steady-state decode pays no host->device transfer)
            self._tables_dev: Optional[jnp.ndarray] = None
            # "pages" (the paged-attention kernel) or "dense" (gather);
            # re-read whenever the store moves (`swap_plan`)
            self.kv_path = kvpool.decode_path(model, _shardings(self.cache))
            self._paged_fn = self._make_paged_decode(self.kv_path)
            self._paged_prefill_fn = kvpool.make_paged_prefill(
                model, self._pax, self._sax)
        else:
            self.pool = None
            self.cache = model.init_cache(n_slots, s_max)
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, dtype=np.int32)
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self.steps = 0
        self.paused = False
        self.seen_prompt_lengths: Dict[int, int] = {}   # length -> last seq
        self._submit_seq = 0
        # jitted single-sequence prefill + batched decode (JIT fallbacks);
        # AOT executables, when installed via swap_plan, take precedence.
        # A paged engine's prefill also writes the request's pages
        # (`kvpool.make_paged_prefill`; the store is donated)
        self._prefill = (jax.jit(self._paged_prefill_fn, donate_argnums=(2,))
                         if self.paged else jax.jit(model.prefill))
        self._decode = (jax.jit(self._paged_fn, donate_argnums=(2,))
                        if self.paged
                        else jax.jit(model.decode_step, donate_argnums=(2,)))
        self._prefill_exec: Dict[int, Callable] = {}
        self._decode_exec: Optional[Callable] = None
        # padded-bucket prefill executables: an unseen prompt length pads
        # to the smallest bucket >= its length instead of JIT-compiling
        self._bucket_exec: Dict[int, Callable] = {}
        self._bucket_lengths: List[int] = []
        # migration-path caches: the per-leaf batch axis of the KV pool is
        # a property of (model, s_max) — constant for the engine's life
        self._batch_axes: Optional[PyTree] = None
        self._migration_warm = False
        # guards executable installation vs the serving path's executable
        # selection: a background PREPARE may commit (swap_plan) from a
        # control thread while step()/_admit() pick executables
        self._exec_lock = threading.Lock()

    def _make_paged_decode(self, kv_path: str) -> Callable:
        return kvpool.make_paged_decode(self.model, self._pax, self._sax,
                                        pages=kv_path == "pages")

    @property
    def role(self) -> str:
        """Disaggregation role (``"unified"``/``"prefill"``/``"decode"``);
        assignment validates fail-closed — an engine with a mistyped role
        would silently fall out of (or into) the routing pool."""
        return self._role

    @role.setter
    def role(self, value: str) -> None:
        if value not in self.ROLES:
            raise ValueError(f"unknown engine role {value!r} "
                             f"(expected one of {self.ROLES})")
        self._role = value

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def pause(self) -> None:
        """Stop stepping. Submissions still queue; nothing is dropped.
        Idempotent; `step()` raises `EngineStateError` while paused."""
        self.paused = True

    def drain(self) -> int:
        """Block until all in-flight device work has retired.

        Returns the number of requests still resident in slots (they resume
        decoding after `resume()` — drain is a device-level barrier, not an
        eviction)."""
        jax.block_until_ready(jax.tree.leaves(self.cache))
        jax.block_until_ready(jax.tree.leaves(self.params))
        return sum(r is not None for r in self.slot_req)

    def swap_plan(self, plan: Optional[ShardingPlan] = None, *,
                  shardings: Optional[Dict[str, Any]] = None,
                  executables: Optional[Dict[str, Any]] = None) -> int:
        """Install a new plan: migrate params/cache onto `shardings` and
        swap in pre-compiled `executables`. Must be called paused — this is
        the blocking window and it performs NO compilation.

        Args:
            plan: the new `ShardingPlan` to record on the engine (routing
                reads it); ``None`` keeps the current plan.
            shardings: ``{"params": sharding tree, "cache": sharding
                tree}`` to `jax.device_put` the live state onto; AOT
                executables compiled for the old layout are invalidated.
            executables: ``{"prefill": callable | {prompt_len: AOT
                executable}, "decode": callable | AOT executable,
                "prefill_buckets": {bucket_len: AOT executable}}`` — a
                plain callable replaces the JIT fallback; an AOT
                dict/executable is installed ahead of the fallback;
                bucket executables serve unseen prompt lengths padded to
                the bucket (see `aot_executables`). A paged engine's
                prefill callables take the fused paged signature
                (`kvpool.make_paged_prefill`).

        Returns:
            The number of bytes migrated (0 without ``shardings``).

        Raises:
            EngineStateError: if the engine is not paused.
        """
        if not self.paused:
            raise EngineStateError("swap_plan requires a paused engine "
                                   "(call pause(); drain() first)")
        migrated = 0
        if shardings is not None:
            migrated = _tree_bytes(self.params) + _tree_bytes(self.cache)
            if "params" in shardings:
                self.params = jax.device_put(self.params, shardings["params"])
            if "cache" in shardings:
                self.cache = jax.device_put(self.cache, shardings["cache"])
            jax.block_until_ready(jax.tree.leaves(self.params))
            jax.block_until_ready(jax.tree.leaves(self.cache))
            with self._exec_lock:
                # executables compiled for the old layout are stale
                self._prefill_exec = {}
                self._decode_exec = None
                self._bucket_exec = {}
                self._bucket_lengths = []
            self._migration_warm = False   # pool-surgery ops too
            if self.paged:
                self._tables_dev = None    # re-place beside the new cache
                kv_path = kvpool.decode_path(self.model,
                                             _shardings(self.cache))
                if kv_path != self.kv_path:
                    self.kv_path = kv_path
                    self._paged_fn = self._make_paged_decode(kv_path)
                    self._decode = jax.jit(self._paged_fn,
                                           donate_argnums=(2,))
        if executables:
            with self._exec_lock:
                pf = executables.get("prefill")
                if isinstance(pf, dict):
                    self._prefill_exec = dict(pf)
                elif pf is not None:
                    self._prefill = pf
                    self._prefill_exec = {}
                bk = executables.get("prefill_buckets")
                if bk is not None:
                    self._bucket_exec = dict(bk)
                    self._bucket_lengths = sorted(self._bucket_exec)
                de = executables.get("decode")
                if isinstance(de, jax.stages.Compiled):
                    self._decode_exec = de
                elif de is not None:      # a jit-wrapped callable: replace
                    self._decode = de     # the fallback outright
                    self._decode_exec = None
        if plan is not None:
            self.plan = plan
        return migrated

    def resume(self) -> None:
        """Leave the paused state and serve again (idempotent)."""
        self.paused = False

    # ------------------------------------------------------------------
    # AOT compilation (PREPARE phase — runs while serving continues)
    # ------------------------------------------------------------------
    def supports_padded_prefill(self) -> bool:
        """Whether bucket-padded prefill is sound for this model: every
        mixer must be attention-style (causal attention never reads the
        padding; positions < ``true_len`` are bit-exact). SSM mixers fold
        the WHOLE padded sequence into their recurrent state, and enc-dec
        prefill has its own shape contract — both are excluded."""
        cfg = self.model.cfg
        if cfg.encdec is not None:
            return False
        from repro.models.lm import layer_kinds   # local: avoid cycles
        return all(mixer in ("attn", "mla") for mixer, _ in layer_kinds(cfg))

    def recent_prompt_lengths(self, cap: Optional[int] = None
                              ) -> Tuple[int, ...]:
        """Snapshot of the most recently seen distinct prompt lengths
        (at most ``cap``, default `MAX_AOT_PREFILL`), sorted ascending.

        A SNAPSHOT, not a live view: safe to hand to a background PREPARE
        thread while request threads keep recording new lengths."""
        cap = cap or self.MAX_AOT_PREFILL
        seen = dict(self.seen_prompt_lengths)    # atomic copy under the GIL
        return tuple(sorted(sorted(seen, key=seen.get)[-cap:]))

    def bucket_lengths(self) -> List[int]:
        """The padded-prefill bucket ladder: powers of two from
        `BUCKET_MIN` up to (and always including) ``s_max``. Empty when
        the model cannot be padded (see `supports_padded_prefill`)."""
        if not self.supports_padded_prefill():
            return []
        out: List[int] = []
        b = self.BUCKET_MIN
        while b < self.s_max:
            out.append(b)
            b *= 2
        out.append(self.s_max)
        return out

    @property
    def has_prefill_buckets(self) -> bool:
        """Whether a padded-bucket prefill ladder is installed (a
        reconfigure keeps it, so unseen prompt lengths never fall back
        to JIT after a swap)."""
        with self._exec_lock:
            return bool(self._bucket_lengths)

    def aot_executables(self, shardings: Dict[str, Any],
                        prefill_lengths: Sequence[int] = (), *,
                        prefill_buckets: bool = False,
                        ) -> Tuple[Dict[str, Any], int]:
        """Ahead-of-time compile decode (and prefill per prompt length)
        against the target `shardings`, via .lower().compile().

        Args:
            shardings: the target ``{"params": ..., "cache": ...}``
                sharding trees (see `plan_to_shardings`).
            prefill_lengths: prompt lengths to compile prefill for; when
                empty, falls back to the engine's most recently seen
                lengths (capped at `MAX_AOT_PREFILL`) — unless a bucket
                ladder is compiled, which serves every length already.
            prefill_buckets: also compile padded-bucket prefill
                executables (`bucket_lengths`) that take a ``true_len``
                argument, so prompt lengths never seen before ALSO avoid
                the JIT fallback on the serving path — an unseen length
                pads to the smallest bucket that holds it. No-op for
                models where padding is unsound (SSM/enc-dec).

        Returns:
            ``(executables, n_compiled)`` in the shape `swap_plan`
            accepts, so the blocking swap window installs finished
            executables only.
        """
        sds = jax.ShapeDtypeStruct
        p_sds = jax.tree.map(lambda x, s: sds(x.shape, x.dtype, sharding=s),
                             self.params, shardings["params"])
        c_sds = jax.tree.map(lambda x, s: sds(x.shape, x.dtype, sharding=s),
                             self.cache, shardings["cache"])
        tok_sds = sds((self.n_slots, 1), jnp.int32)
        pos_sds = sds((self.n_slots,), jnp.int32)
        # the donated store leaves each step exactly as it came in (left
        # to propagation, a multi-device layout could drift and the next
        # call would no longer match its executable); logits replicate
        out_sh = (_replicated(shardings["cache"]), shardings["cache"])
        if self.paged:
            tbl_sds = sds((self.n_slots, self.pages_per_seq), jnp.int32)
            fn = self._make_paged_decode(
                kvpool.decode_path(self.model, shardings["cache"]))
            decode = jax.jit(fn, donate_argnums=(2,),
                             out_shardings=out_sh) \
                .lower(p_sds, tok_sds, c_sds, pos_sds, tbl_sds).compile()
        else:
            decode = jax.jit(self.model.decode_step, donate_argnums=(2,),
                             out_shardings=out_sh) \
                .lower(p_sds, tok_sds, c_sds, pos_sds).compile()
        n_compiled = 1

        def batch_sds(S: int, padded: bool) -> Dict[str, Any]:
            b = {"tokens": sds((1, S), jnp.int32)}
            if padded:
                b["true_len"] = sds((), jnp.int32)
            if self.model.cfg.pos_type == "mrope":
                b["positions"] = sds((3, 1, S), jnp.int32)
            return b

        if self.paged:
            row_sds = sds((self.pages_per_seq,), jnp.int32)
            prefill_jit = jax.jit(self._paged_prefill_fn, donate_argnums=(2,),
                                  out_shardings=out_sh)

            def compile_prefill(S: int, padded: bool):
                return prefill_jit.lower(p_sds, batch_sds(S, padded),
                                         c_sds, row_sds).compile()
        else:
            def compile_prefill(S: int, padded: bool):
                return jax.jit(self.model.prefill) \
                    .lower(p_sds, batch_sds(S, padded)).compile()

        prefill: Dict[int, Callable] = {}
        if prefill_lengths:
            lengths = sorted(set(prefill_lengths))
        elif prefill_buckets and self.bucket_lengths():
            lengths = []      # the ladder already serves every length
        else:
            # most recently seen distinct lengths, capped (see MAX_AOT_PREFILL)
            lengths = list(self.recent_prompt_lengths())
        for S in lengths:
            prefill[S] = compile_prefill(S, padded=False)
            n_compiled += 1
        buckets: Dict[int, Callable] = {}
        if prefill_buckets:
            for S in self.bucket_lengths():
                buckets[S] = compile_prefill(S, padded=True)
                n_compiled += 1
        return {"prefill": prefill, "decode": decode,
                "prefill_buckets": buckets}, n_compiled

    def decode_hlo_text(self) -> str:
        """Post-compile HLO of the decode step, for compiled-artifact
        validation (`ServingCluster` checks registered engines' HLO
        against route constraints, not just their declared plans).

        Reuses the installed AOT executable when present; otherwise
        compiles decode once for the live layout and installs it, so the
        check never forces a later JIT on the serving path."""
        with self._exec_lock:
            exec_ = self._decode_exec
        if exec_ is None:
            tok = jax.ShapeDtypeStruct((self.n_slots, 1), jnp.int32)
            pos = jax.ShapeDtypeStruct((self.n_slots,), jnp.int32)
            if self.paged:
                tbl = jax.ShapeDtypeStruct(
                    (self.n_slots, self.pages_per_seq), jnp.int32)
                exec_ = jax.jit(self._paged_fn, donate_argnums=(2,)) \
                    .lower(self.params, tok, self.cache, pos, tbl).compile()
            else:
                exec_ = jax.jit(self.model.decode_step,
                                donate_argnums=(2,)) \
                    .lower(self.params, tok, self.cache, pos).compile()
            with self._exec_lock:
                if self._decode_exec is None:
                    self._decode_exec = exec_
        return exec_.as_text()

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Enqueue a request (stamps ``t_submit``; records its prompt
        length for future AOT prefill compilation). Works while paused —
        the request waits for `resume()`."""
        req.t_submit = time.time()
        self.note_prompt_length(len(req.prompt))
        self.queue.append(req)
        rec = obs_events.RECORDER
        if rec is not None:
            rec.emit("request.submit", engine=self.obs_name, rid=req.rid,
                     label=req.labels.get("data-type", ""),
                     prompt_len=len(req.prompt),
                     max_new_tokens=req.max_new_tokens)

    def note_prompt_length(self, length: int) -> None:
        """Record a prompt length as recently seen (feeds the default AOT
        prefill set) WITHOUT re-stamping submission metadata — used when a
        request migrates onto this engine from another one."""
        self._submit_seq += 1
        self.seen_prompt_lengths[length] = self._submit_seq

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    @property
    def load(self) -> int:
        """Queued + resident requests (the router's balance key)."""
        return len(self.queue) + sum(r is not None for r in self.slot_req)

    @property
    def free_slots(self) -> int:
        """Decode lanes currently unoccupied (decode-width capacity;
        token-granular memory capacity is `free_tokens`)."""
        return sum(r is None for r in self.slot_req)

    # -- token-granular capacity / fragmentation accounting ------------
    @property
    def kv_token_capacity(self) -> int:
        """Total KV tokens this engine can hold for admissions. Never
        negative: a pool whose watermark swallows every page (or a
        zero-page pool) reports 0 capacity, not a negative number that
        would poison the autoscaler's aggregate capacity sums."""
        if self.paged:
            return max(self.pool.n_pages - self.pool.watermark, 0) \
                * self.page_size
        return self.n_slots * self.s_max

    @property
    def free_tokens(self) -> int:
        """KV tokens still available to admissions (paged: admittable
        pages x page size; slot-granular: free slots x ``s_max``).
        Clamped to >= 0 — the rebalance-over-spawn decision sums this
        across peers and a negative entry would hide real capacity."""
        if self.paged:
            return max(self.pool.admittable_pages, 0) * self.page_size
        return self.free_slots * self.s_max

    @property
    def kv_allocated_tokens(self) -> int:
        """KV tokens reserved by resident requests (paged: their pages;
        slot-granular: a full ``s_max`` per occupied slot)."""
        if self.paged:
            return self.pool.allocated_tokens
        return sum(r is not None for r in self.slot_req) * self.s_max

    @property
    def kv_used_tokens(self) -> int:
        """KV tokens actually written by resident requests (the decode
        positions) — the numerator of `kv_utilization`."""
        return int(sum(int(self.slot_pos[i])
                       for i, r in enumerate(self.slot_req)
                       if r is not None))

    @property
    def kv_utilization(self) -> float:
        """Used / allocated KV tokens — the slot-padding-waste signal
        the planner and autoscaler read. 0.0 when nothing is resident;
        right-sized page reservations push it toward 1.0, full-``s_max``
        slot pinning keeps it low for short requests."""
        alloc = self.kv_allocated_tokens
        return self.kv_used_tokens / alloc if alloc else 0.0

    def admission_tokens(self, need: int) -> int:
        """Token capacity that admitting a request with a ``need``-token
        extent would consume here (page-rounded; a slot engine always
        spends a full slot)."""
        if self.paged:
            return self.pool.pages_for(min(need, self.s_max)) \
                * self.page_size
        return self.s_max

    def fits_inflight(self, needs: Sequence[int]) -> bool:
        """Migration pre-flight: can decoding requests with these
        capacity needs (tokens each) be imported right now — lanes AND
        memory? Imports may spend the watermark headroom (that is what
        it is reserved for), so the page budget here is the full free
        list, not `free_tokens`."""
        if len(needs) > self.free_slots:
            return False
        if self.paged:
            pages = sum(self.pool.pages_for(min(n, self.s_max))
                        for n in needs)
            return pages <= self.pool.free_pages
        return True

    @property
    def cache_batch(self) -> int:
        """Batch dim of the live KV tree (`plan_to_shardings` sizing):
        the page count for a paged pool, ``n_slots`` otherwise."""
        return self.pool.store_batch if self.paged else self.n_slots

    def single_layout(self) -> PyTree:
        """Shape tree of one request's single-sequence KV in this
        engine's layout (the migration fit target): the page-rounded
        extent for a paged pool, ``s_max`` for a slot pool."""
        S = self.pages_per_seq * self.page_size if self.paged else self.s_max
        return self.model.cache_shapes(1, S)

    def _admit(self) -> None:
        while self.queue:
            slot = self._free_slot()
            if slot is None:
                return
            # attribution stamps: NON-advancing reads on the recording
            # clock (obs_events.now), so lineage's admission/prefill
            # split never perturbs a simulated run — under a FakeClock
            # both components are exactly 0 and queue wait carries the
            # simulated story; under the wall clock they are real.
            rec = obs_events.RECORDER
            t_adm0 = obs_events.now() if rec is not None else 0.0
            pages: List[int] = []
            if self.paged:
                head = self.queue[0]
                need = min(len(head.prompt) + head.max_new_tokens,
                           self.s_max)
                try:
                    pages = self.pool.alloc(self.pool.pages_for(need))
                except kvpool.PoolOOM:
                    return    # fail closed: stays queued, FIFO order kept
            req = self.queue.pop(0)
            # non-advancing read of t_submit's clock: a stamp that only
            # measures never moves a simulated run
            req.t_admit = obs_events.now()
            S = len(req.prompt)
            # inputs are built on the host (numpy): an eager device op
            # here would compile per prompt length on the serving path
            prompt = np.asarray(req.prompt, np.int32)[None, :]
            # exact-length AOT executable first; else the smallest padded
            # bucket that holds the prompt; JIT fallback last. Selected
            # under the exec lock: a background PREPARE commit must never
            # be observed half-installed.
            batch: Dict[str, Any] = {"tokens": prompt}
            with self._exec_lock:
                prefill = self._prefill_exec.get(S)
                if prefill is None:
                    bucket = next((b for b in self._bucket_lengths
                                   if b >= S), None)
                    if bucket is not None:
                        batch = {"tokens": np.pad(
                                     prompt, ((0, 0), (0, bucket - S))),
                                 "true_len": np.int32(S)}
                        prefill = self._bucket_exec[bucket]
                    else:
                        prefill = self._prefill
            if self.model.cfg.pos_type == "mrope":
                Sp = batch["tokens"].shape[1]
                batch["positions"] = np.broadcast_to(
                    np.arange(Sp, dtype=np.int32)[None, None], (3, 1, Sp))
            if self.paged:
                # the page-table row is final before prefill: the fused
                # paged prefill writes the cache straight into these
                # pages; the scratch-padded tail absorbs bucket slack
                # (never read: decode masks by position)
                row = pages + [kvpool.SCRATCH_PAGE] \
                    * (self.pages_per_seq - len(pages))
            with annotate("engine.prefill", prompt_len=S,
                          bucket=batch["tokens"].shape[1]):
                t_pre0 = obs_events.now() if rec is not None else 0.0
                if self.paged:
                    logits, self.cache = prefill(
                        self.params, batch, self.cache,
                        np.asarray(row, np.int32))
                else:
                    logits, cache1 = prefill(self.params, batch)
                with annotate("engine.logits"):
                    logits = np.asarray(logits)
                with annotate("engine.sample"):
                    tok = int(np.argmax(
                        logits[0, : self.vocab].astype(np.float32)))
                t_pre1 = obs_events.now() if rec is not None else 0.0
            req.tokens_out.append(tok)
            req.t_first = time.time()
            if rec is not None:
                rec.emit("request.admit", engine=self.obs_name, rid=req.rid,
                         label=req.labels.get("data-type", ""),
                         queue_wait_s=req.t_admit - req.t_submit,
                         admit_s=max(0.0, t_pre0 - t_adm0),
                         prefill_s=max(0.0, t_pre1 - t_pre0),
                         role=self.role)
            if self.paged:
                self.page_tables[slot] = row
                self.slot_pages[slot] = pages
                self._tables_dev = None
            else:
                # merge the single-sequence cache into the slot pool
                # (bucket entries beyond S are never read: masked)
                self.cache = _write_slot(self.cache, cache1, slot,
                                         S, self.s_max)
            self.slot_req[slot] = req
            self.slot_pos[slot] = S

    def _release_lane(self, slot: int) -> None:
        """Clear lane bookkeeping; a paged lane returns its pages to the
        pool the moment the request retires (token-granular free)."""
        self.slot_req[slot] = None
        self.slot_pos[slot] = 0
        if self.paged:
            self.pool.free(self.slot_pages[slot])
            self.slot_pages[slot] = []
            self.page_tables[slot] = kvpool.SCRATCH_PAGE
            self._tables_dev = None

    def _compact(self) -> None:
        """Pack active requests into the lowest decode lanes (continuous
        batching: a request owns PAGES, not a lane, so lane assignment
        is re-derived every step and the decode batch stays dense). The
        page-table rows travel with their requests; per-request streams
        are row-order independent (decode is row-wise)."""
        order = [i for i, r in enumerate(self.slot_req) if r is not None]
        if order == list(range(len(order))):
            return
        n = len(order)
        req = [self.slot_req[i] for i in order]
        pos = [int(self.slot_pos[i]) for i in order]
        pages = [self.slot_pages[i] for i in order]
        tables = self.page_tables[order].copy()
        self.slot_req = req + [None] * (self.n_slots - n)
        self.slot_pos[:] = 0
        self.slot_pos[:n] = pos
        self.slot_pages = pages + [[] for _ in range(self.n_slots - n)]
        self.page_tables[:] = kvpool.SCRATCH_PAGE
        self.page_tables[:n] = tables
        self._tables_dev = None

    # ------------------------------------------------------------------
    # live migration (export / import one request's state)
    # ------------------------------------------------------------------
    def _migration_axes(self) -> PyTree:
        """Per-leaf batch-axis tree of the KV pool (cached — a property
        of the model and ``s_max``, not of the current layout)."""
        if self._batch_axes is None:
            self._batch_axes = migration.batch_axis_tree(self.model,
                                                         self.s_max)
        return self._batch_axes

    def warm_migration(self) -> None:
        """Pre-compile the pool-surgery ops the migration path uses
        (slot slice + slot write at the live shapes/dtypes), so a later
        `export_slot`/`import_slot` pays no first-call compile — the same
        compile-ahead discipline `swap_plan` applies to executables.
        Idempotent and state-preserving (results are discarded)."""
        if self._migration_warm:
            return
        if self.paged:
            # mirror the paged export→import pipeline: full-width table
            # gather, fit to the page-rounded single layout, place, and
            # two chained full-width page scatters (results discarded —
            # scratch-row writes only ever touch page 0)
            row = np.full((1, self.pages_per_seq), kvpool.SCRATCH_PAGE,
                          dtype=np.int32)
            kv = kvpool.gather_pages(self.cache, jnp.asarray(row),
                                     self._pax, self._sax)
            jax.block_until_ready(jax.tree.leaves(kv))
            single = migration.fit_single(kv, self.single_layout())
            single = migration.place_like(single, self.cache)
            scratch_row = [kvpool.SCRATCH_PAGE] * self.pages_per_seq
            w1 = kvpool.write_pages(self.cache, single, scratch_row,
                                    self._pax, self._sax)
            w2 = kvpool.write_pages(w1, single, scratch_row,
                                    self._pax, self._sax)
            jax.block_until_ready(jax.tree.leaves(w2))
            self._migration_warm = True
            return
        axes = self._migration_axes()
        # mirror the real export→import pipeline exactly (fit/place change
        # the arrays' committed-ness, which is part of the op-cache key)
        kv = migration.slice_slot(self.cache, axes, 0)
        jax.block_until_ready(jax.tree.leaves(kv))
        single = migration.fit_single(kv, self.model.cache_shapes(1,
                                                                  self.s_max))
        single = migration.place_like(single, self.cache)
        # chain two writes: the pool operand's placement differs between
        # the first import (fresh pool) and later ones (previous write's
        # output) — both variants must be compiled before the window
        w1 = migration.write_single(self.cache, single, axes, 0)
        w2 = migration.write_single(w1, single, axes, 0)
        jax.block_until_ready(jax.tree.leaves(w2))
        self._migration_warm = True

    def export_slot(self, rid: int) -> SlotSnapshot:
        """Detach request ``rid`` from this engine as a `SlotSnapshot`.

        A resident request's KV slices are sliced out of the pool (its
        slot is freed); a queued request exports as a lightweight
        ``phase="queued"`` snapshot. In both cases ``max_new_tokens`` is
        clamped to what THIS pool could still have produced, so importing
        into a larger pool never extends the stream beyond the
        unmigrated run's.

        Returns:
            The snapshot (the `Request` object travels inside it — it is
            no longer tracked by this engine).

        Raises:
            KeyError: ``rid`` is neither resident nor queued here.
        """
        for slot, r in enumerate(self.slot_req):
            if r is not None and r.rid == rid:
                pos = int(self.slot_pos[slot])
                room = self.s_max - 1 - pos
                if r.max_new_tokens - len(r.tokens_out) > room:
                    r.max_new_tokens = len(r.tokens_out) + room
                if self.paged:
                    # gather the request's pages into the standard
                    # single-sequence snapshot layout (full-width table:
                    # scratch-padded tail positions are >= pos — masked
                    # on the importer, so one static gather shape
                    # serves every export)
                    kv = kvpool.gather_pages(
                        self.cache,
                        jnp.asarray(self.page_tables[slot][None, :]),
                        self._pax, self._sax)
                else:
                    kv = migration.slice_slot(self.cache,
                                              self._migration_axes(), slot)
                jax.block_until_ready(jax.tree.leaves(kv))
                self._release_lane(slot)
                return SlotSnapshot(rid=rid, request=r, phase="decoding",
                                    pos=pos, kv=kv, src_s_max=self.s_max)
        for i, r in enumerate(self.queue):
            if r.rid == rid:
                self.queue.pop(i)
                r.max_new_tokens = min(r.max_new_tokens,
                                       self.s_max - len(r.prompt))
                return SlotSnapshot(rid=rid, request=r, phase="queued",
                                    pos=len(r.prompt), kv=None,
                                    src_s_max=self.s_max)
        raise KeyError(f"request {rid} is not on this engine")

    def import_slot(self, snapshot: SlotSnapshot, *,
                    kv_fitted: Optional[PyTree] = None) -> int:
        """Adopt a migrated request: re-queue a ``"queued"`` snapshot, or
        write a ``"decoding"`` snapshot's KV into a free lane (refit to
        this pool's single-sequence layout and `jax.device_put` onto it;
        a paged pool additionally reserves the request's pages — spending
        the watermark headroom if needed) and resume decode at the
        snapshot position — no recompilation, no re-run of prefill.
        Submission stamps are preserved: TTFT/TPOT still measure from
        the original submit.

        Args:
            kv_fitted: the snapshot's KV already fitted to this engine's
                `single_layout` and placed on its sharding — the batched
                multi-request transfer (`migration.migrate_many`) does
                one device_put for the whole cohort and hands each
                request its placed tree here.

        Returns:
            KV bytes written into the pool (0 for a queued snapshot).

        Raises:
            MigrationError: fail-closed, with this engine unchanged —
                the pool's sequence capacity cannot finish the request's
                generation (e.g. migrating into a smaller ``s_max``), no
                decode lane is free, or the paged pool is out of pages.
        """
        need = migration.required_capacity(snapshot)
        if need > self.s_max:
            raise MigrationError(
                f"request {snapshot.rid} needs sequence capacity {need} "
                f"but this pool has s_max={self.s_max} — failing closed")
        req = snapshot.request
        if snapshot.phase == "queued":
            self.note_prompt_length(len(req.prompt))
            self.queue.append(req)
            return 0
        slot = self._free_slot()
        if slot is None:
            raise MigrationError(
                f"no free decode slot for request {snapshot.rid} "
                f"(n_slots={self.n_slots}) — failing closed")
        if kv_fitted is not None:
            single = kv_fitted
        else:
            single = migration.fit_single(snapshot.kv, self.single_layout())
            single = migration.place_like(single, self.cache)
        if self.paged:
            try:
                pages = self.pool.alloc(self.pool.pages_for(need),
                                        reserve=True)
            except kvpool.PoolOOM as e:
                raise MigrationError(str(e)) from e
            # full-width write (scratch-padded tail): one static scatter
            # shape serves every import; tail garbage goes to page 0
            row = pages + [kvpool.SCRATCH_PAGE] \
                * (self.pages_per_seq - len(pages))
            self.cache = kvpool.write_pages(self.cache, single, row,
                                            self._pax, self._sax)
            self.page_tables[slot] = row
            self.slot_pages[slot] = pages
            self._tables_dev = None
        else:
            self.cache = migration.write_single(
                self.cache, single, self._migration_axes(), slot)
        jax.block_until_ready(jax.tree.leaves(self.cache))
        self.slot_req[slot] = req
        self.slot_pos[slot] = snapshot.pos
        self.note_prompt_length(len(req.prompt))
        return snapshot.nbytes

    # ------------------------------------------------------------------
    def step(self) -> int:
        """Admit queued requests into free slots (prefill), then run one
        decode step over all active slots.

        Returns:
            The number of slots that decoded this step.

        Raises:
            EngineStateError: if the engine is paused.
        """
        if self.paused:
            raise EngineStateError("engine is paused (resume() to serve)")
        with annotate("engine.step"):
            with annotate("engine.admit"):
                self._admit()
            if self.paged:
                with annotate("engine.compact"):
                    self._compact()
            active = [i for i, r in enumerate(self.slot_req)
                      if r is not None]
            if not active:
                return 0
            if not self.paged:
                span_args = {}
            elif self.kv_path == "pages":    # pages through each new entry
                span_args = {"kv_path": "pages", "kv_pages": sum(
                    (int(self.slot_pos[i]) + self.page_size)
                    // self.page_size for i in active)}
            else:
                span_args = {"kv_path": "dense",
                             "kv_pages": self.n_slots * self.pages_per_seq}
            with annotate("engine.decode", **span_args):
                tokens = np.zeros((self.n_slots, 1), dtype=np.int32)
                for i in active:
                    tokens[i, 0] = self.slot_req[i].tokens_out[-1]
                # per-slot positions (inactive slots write harmlessly at
                # index 0 — their slot is re-prefilled before reuse; paged
                # inactive lanes point at the scratch page)
                pos = jnp.asarray(self.slot_pos, dtype=jnp.int32)
                with self._exec_lock:
                    decode = self._decode_exec or self._decode
                if self.paged:
                    if self._tables_dev is None:
                        self._tables_dev = jnp.asarray(self.page_tables)
                    logits, self.cache = decode(
                        self.params, jnp.asarray(tokens), self.cache, pos,
                        self._tables_dev)
                else:
                    logits, self.cache = decode(
                        self.params, jnp.asarray(tokens), self.cache, pos)
            with annotate("engine.logits"):
                jax.block_until_ready(logits)
                logits = np.asarray(logits)
            # vocab slice and argmax on the host: no eager device op (and
            # so no compile) on the serving path
            with annotate("engine.sample"):
                logits = logits[:, : self.vocab].astype(np.float32)
                now = time.time()
                rec = obs_events.RECORDER
                for i in active:
                    req = self.slot_req[i]
                    tok = int(np.argmax(logits[i]))
                    req.tokens_out.append(tok)
                    self.slot_pos[i] += 1
                    if (len(req.tokens_out) >= req.max_new_tokens
                            or self.slot_pos[i] >= self.s_max - 1):
                        req.t_done = now
                        self.done.append(req)
                        self._release_lane(i)
                        if rec is not None:
                            rec.emit("request.complete",
                                     engine=self.obs_name, rid=req.rid,
                                     label=req.labels.get("data-type", ""),
                                     ttft_s=req.ttft, tpot_s=req.tpot,
                                     tokens_out=len(req.tokens_out),
                                     role=self.role)
                self.steps += 1
            return len(active)

    def run(self, max_steps: int = 10_000) -> None:
        """Step until the queue and all slots are empty (or the engine's
        lifetime step count reaches ``max_steps``).

        Raises:
            EngineStateError: if the engine is paused.
        """
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and self.steps < max_steps:
            self.step()

    # ------------------------------------------------------------------
    def metrics(self) -> Dict[str, float]:
        """Full `METRIC_KEYS` summary over everything completed so far."""
        return compute_metrics(self.done)


def _shardings(tree: PyTree) -> PyTree:
    return jax.tree.map(lambda x: x.sharding, tree)


def _replicated(shardings: PyTree) -> Any:
    """A fully replicated sharding on the devices of a sharding tree."""
    leaf = jax.tree.leaves(shardings)[0]
    if isinstance(leaf, NamedSharding):
        return NamedSharding(leaf.mesh, PartitionSpec())
    return leaf


def _tree_bytes(tree: PyTree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _write_slot(pool: PyTree, single: PyTree, slot: int, prompt_len: int,
                s_max: int) -> PyTree:
    """Write a 1-sequence prefill cache into batch slot `slot` of the pool."""

    def one(p, c):
        # locate batch dim: first dim where pool==n_slots and cache==1
        for ax in range(min(p.ndim, c.ndim)):
            if p.shape[ax] != c.shape[ax] and c.shape[ax] == 1:
                batch_ax = ax
                break
        else:
            return p
        # seq dims may differ (prompt_len vs s_max): pad cache to pool shape
        pads = []
        for ax in range(p.ndim):
            if ax == batch_ax:
                pads.append((0, 0))
            else:
                pads.append((0, p.shape[ax] - c.shape[ax]))
        c_pad = jnp.pad(c.astype(p.dtype), pads)
        idx = [slice(None)] * p.ndim
        idx[batch_ax] = slice(slot, slot + 1)
        return p.at[tuple(idx)].set(c_pad)

    return jax.tree.map(one, pool, single)
