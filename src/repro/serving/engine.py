"""The protected serving engine: continuous batching over plan lanes.

:class:`ServingEngine` turns the repo's models into a request-serving
stack.  Tenants (traffic classes) carry their own
:class:`~repro.protect.ProtectionPlan` — per-tenant policies and
thresholds, the V-ABFT direction — and tenants sharing a plan share a
**lane**: one jitted prefill/decode pair compiled against that plan (the
plan rides in the jit-static ``Ctx``, so distinct plans are necessarily
distinct compiled programs) and one fixed-slot continuous batcher.

Per engine iteration:

1. arrivals whose (virtual) time has come enter the admission queue;
2. each lane fills its free decode slots FIFO from the queue and runs a
   batch=1 prefill per admission (first token = TTFT), inserting the
   request's KV state into its slot of the lane's batched cache;
3. each lane with active slots runs ONE batched decode step; detect→act
   policies run inside (recompute retries, correct, abort — an abort
   fails the lane's in-flight requests, never the server);
4. finished requests retire, freeing slots for the next iteration.

The clock is hybrid: arrivals are simulated offsets, service time is the
measured wall time of the jitted steps (compiles are excluded via
:meth:`warmup`), so SLO percentiles reflect real compute under the
chosen protection plans.

Every call of :meth:`ServingEngine.run` is timed as host spans
(:class:`repro.obs.span`) on the profiler's clock, so a ``jax.profiler``
trace shows what the host did around each of the device's ops:
``serving/run`` (the whole call), ``serving/upload`` (a DLRM request's
arrays sent to the device), ``serving/dispatch`` (launching a jitted
step), ``serving/wait`` (blocked until it finishes) and
``serving/readback`` (its outputs and FaultReport counters read back).
Each blocking device-to-host read is counted on the step's
:class:`StepEvent` (``host_fetches``).

Fault injection is first-class: a :class:`FaultInjection` flips a bit in
a plan-path-addressed weight leaf right before a chosen step and — unless
``persistent`` — restores the clean weight right after it, so a
recompute-policy retry measures one *transient* upset, not a permanently
corrupted model.  Detection shows up in the same telemetry timeline as
the latency it costs.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.obs.trace import span
from repro.serving.batcher import ContinuousBatcher, Slot
from repro.serving.queue import AdmissionQueue
from repro.serving.telemetry import (InjectionRecord, RequestRecord,
                                     StepEvent, Telemetry)
from repro.serving.workload import Request


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One traffic class: its protection plan and relative traffic share."""
    name: str
    plan: object = None            # ProtectionPlan (None = default_plan())
    weight: float = 1.0

    def resolved_plan(self):
        from repro.protect import default_plan
        return self.plan if self.plan is not None else default_plan()


@dataclasses.dataclass
class FaultInjection:
    """Flip one bit before global step ``step``.

    ``target="weights"`` flips a bit of a plan-path-addressed weight leaf
    (restored after the step unless ``persistent``).  ``target="kv"``
    flips one int8 payload byte of a resident request's KV cache — a
    memory-resident fault, inherently persistent until the row is
    overwritten, the page evicted, or the cache dropped
    (:meth:`ServingEngine.reset_state`); ``victim`` is ignored and the
    flip location is drawn from ``seed``."""
    step: int
    victim: Optional[str] = None   # dotted-path pattern (core.inject)
    persistent: bool = False
    seed: int = 0
    target: str = "weights"        # "weights" | "kv"


def tenant_weights(tenants: Sequence[TenantSpec]) -> Dict[str, float]:
    return {t.name: t.weight for t in tenants}


def _counters_of(metrics: dict, fetch) -> tuple:
    """(per-op int counters, total residual errors) from step metrics,
    each read to the host as ``fetch(int, value)``."""
    from repro.core.policy import op_kinds

    def read(key):
        v = metrics.get(key)
        return 0 if v is None else fetch(int, v)

    out: Dict[str, int] = {}
    errors = 0
    for k in op_kinds():
        c = read(f"abft/{k}_checks")
        e = read(f"abft/{k}_errors")
        out[f"{k}_checks"] = c
        out[f"{k}_errors"] = e
        errors += e
    out["retries"] = read("abft/retries")
    out["corrections"] = read("abft/corrections")
    return out, errors


class _Lane:
    """One protection plan's slice of the engine: jitted steps + batcher +
    the jax-side decode state (cache / last tokens / positions)."""

    def __init__(self, key: str, plan, tenants: List[str], n_slots: int):
        self.key = key
        self.plan = plan
        self.tenants = set(tenants)
        self.batcher = ContinuousBatcher(n_slots)
        self.n_slots = n_slots
        self.cache = None
        self.tokens = None
        self.pos = None
        self.prefill_fn = None
        self.decode_fn = None
        self.insert_fn = None
        self.forward_fn = None         # dlrm one-shot lanes
        # paged-KV lanes (engine fills these when paging is configured)
        self.pager = None              # PagedKVManager
        self.n_layers = 0
        self.table_fn = None
        self.reset_fn = None
        self.scrub_fn = None

    def accepts(self, req: Request) -> bool:
        return req.tenant in self.tenants

    def reset(self):
        """Drop all jax-side state (post-abort lane reset)."""
        self.cache = None
        self.tokens = None
        self.pos = None
        if self.pager is not None:
            self.pager.reset()
        return self.batcher.drain()


class ServingEngine:
    def __init__(self, cfg, tenants: Sequence[TenantSpec], *,
                 n_slots: int = 4, max_prompt: int = 64,
                 max_new_tokens: int = 32, queue_depth: int = 0,
                 seed: int = 0, compute_dtype=None,
                 dlrm_extras=None, paging=None):
        import jax
        import jax.numpy as jnp

        from repro.models.base import build_model
        from repro.sharding import values_of

        if not tenants:
            raise ValueError("need at least one TenantSpec")
        names = [t.name for t in tenants]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate tenant names in {names}")

        self.cfg = cfg
        self.tenants = {t.name: t for t in tenants}
        self.n_slots = n_slots
        self.max_prompt = max_prompt
        self.max_new_tokens = max_new_tokens
        self.queue = AdmissionQueue(max_depth=queue_depth)
        self.clock_s = 0.0
        self.global_step = 0
        self._compute_dtype = (jnp.bfloat16 if compute_dtype is None
                               else compute_dtype)
        #: applied-injection stack: [(leaf_idx, clean_leaf, persistent)]
        #: in application order — restores pop in reverse so an earlier
        #: fault's clean copy survives a later fault on the same leaf
        self._injection_state: list = []
        self._warm = False
        #: Observability bundle for the CURRENT run (set by run(obs=...))
        self._obs = None
        #: detection-health Monitor for the CURRENT run (run(monitor=...))
        self._monitor = None
        #: AdaptiveThresholds controller bundle (run(adapt=...))
        self._adapt = None
        #: lane keys whose plan was already escalated (one-way per engine)
        self._escalated = set()
        #: blocking device-to-host reads since the last step event
        self._fetches = 0
        #: tracer time at which the current step was dispatched
        self._dispatch_s = 0.0
        #: a DLRM step's ``eb_slots`` by (lane, bag shape): fixed by the
        #: shapes, so read to the host once (at warmup where it can be)
        self._eb_slots: Dict[tuple, int] = {}

        #: PagingConfig | None — paged, prefix-shared, per-page-checksummed
        #: KV mode.  Prompts round up to page-multiple buckets, slots hold
        #: page tables into a lane-shared pool, admission runs a
        #: prefix-tree lookup, retire frees non-shared pages.
        self.paging = paging
        if paging is not None:
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"paged KV serves attention-only decode caches; "
                    f"family {cfg.family!r} is not supported")
            if cfg.meta_tokens:
                raise ValueError("paged KV assumes positions start at 0 "
                                 "(meta_tokens must be 0)")
            p = paging.page_size
            self._max_bucket = -(-max_prompt // p) * p
            mp_per_slot = (self._max_bucket + max_new_tokens - 1) // p + 1
            if mp_per_slot > paging.n_pages:
                raise ValueError(
                    f"pool of {paging.n_pages} pages cannot hold even one "
                    f"slot's {mp_per_slot} pages")
        self.is_dlrm = cfg.family == "dlrm"
        if self.is_dlrm:
            from repro.configs.dlrm import EXTRAS
            self.dlrm_extras = dlrm_extras if dlrm_extras is not None \
                else EXTRAS
            from repro.models.dlrm import init_dlrm
            self.model = None
            self.cache_len = 0
            self.params = values_of(jax.jit(
                functools.partial(init_dlrm, ex=self.dlrm_extras,
                                  quant=True,
                                  table_rows=self.dlrm_extras.table_rows)
            )(jax.random.key(seed)))
        else:
            extra = cfg.meta_tokens + 8
            if cfg.family == "vlm":
                extra += cfg.n_patches
            self.cache_len = max_prompt + max_new_tokens + extra
            if paging is not None:
                self.cache_len = self._max_bucket + max_new_tokens + extra
            self.model = build_model(cfg, max_pos=self.cache_len + 8)
            self.params = values_of(jax.jit(
                lambda k: self.model.init(k, quant=True)
            )(jax.random.key(seed)))

        # ------------------------- plan lanes --------------------------------
        by_plan: Dict[str, List[TenantSpec]] = {}
        for t in tenants:
            by_plan.setdefault(t.resolved_plan().describe(), []).append(t)
        self.lanes: List[_Lane] = []
        for i, (pkey, specs) in enumerate(sorted(by_plan.items())):
            lane = _Lane(key=f"lane{i}[{specs[0].resolved_plan().name or pkey}]",
                         plan=specs[0].resolved_plan(),
                         tenants=[t.name for t in specs],
                         n_slots=n_slots)
            if paging is not None:
                from repro.paging import PagedKVManager
                p = paging.page_size
                lane.pager = PagedKVManager(
                    paging, n_slots,
                    (self._max_bucket + max_new_tokens - 1) // p + 1)
            self._build_lane_fns(lane)
            self.lanes.append(lane)
        self._lane_of = {name: lane for lane in self.lanes
                         for name in lane.tenants}

    # ------------------------------ compiled steps ---------------------------

    def _build_lane_fns(self, lane: _Lane) -> None:
        import jax
        import jax.numpy as jnp

        from repro.protect import protect

        if self.is_dlrm:
            from repro.models.dlrm import dlrm_forward
            fwd_p = protect(
                functools.partial(dlrm_forward, ex=self.dlrm_extras),
                lane.plan, compute_dtype=self._compute_dtype)

            @jax.jit
            def forward(params, dense, bags):
                logit, rep = fwd_p(params, dense, bags)
                return logit, rep.as_metrics()

            lane.forward_fn = forward
            return

        cfg = self.cfg
        prefill_p = protect(self.model.prefill, lane.plan,
                            compute_dtype=self._compute_dtype)
        decode_p = protect(self.model.decode, lane.plan,
                           compute_dtype=self._compute_dtype)

        @jax.jit
        def decode(params, cache, tokens, pos):
            (logits, new_cache), rep = decode_p(params, cache, tokens, pos)
            tok = jnp.argmax(logits[..., :cfg.vocab],
                             axis=-1).astype(jnp.int32)
            return tok, new_cache, rep.as_metrics()

        lane.decode_fn = decode

        if self.paging is not None:
            from repro.paging import (pack_prompt_pages, reset_pages,
                                      scrub_cache)

            # prefill compiles once per prompt bucket (cache_len static)
            @functools.partial(jax.jit, static_argnums=(2,))
            def prefill_paged(params, batch, cache_len):
                (logits, cache), rep = prefill_p(params, batch,
                                                 cache_len=cache_len)
                tok = jnp.argmax(logits[..., :cfg.vocab],
                                 axis=-1).astype(jnp.int32)
                return tok, cache, rep.as_metrics()

            @jax.jit
            def insert_pages(cache, one, page_ids, table):
                attn = cache["attn"]
                k = pack_prompt_pages(attn["k"], one["attn"]["k"], page_ids)
                v = pack_prompt_pages(attn["v"], one["attn"]["v"], page_ids)
                return {**cache, "attn": {"k": k._replace(table=table),
                                          "v": v._replace(table=table)}}

            @jax.jit
            def set_table(cache, table):
                attn = cache["attn"]
                return {**cache, "attn": {
                    "k": attn["k"]._replace(table=table),
                    "v": attn["v"]._replace(table=table)}}

            @jax.jit
            def reset_tail(cache, page_ids):
                attn = cache["attn"]
                return {**cache, "attn": {
                    "k": reset_pages(attn["k"], page_ids),
                    "v": reset_pages(attn["v"], page_ids)}}

            lane.prefill_fn = prefill_paged
            lane.insert_fn = insert_pages
            lane.table_fn = set_table
            lane.reset_fn = reset_tail
            lane.scrub_fn = jax.jit(scrub_cache)
            return

        @jax.jit
        def prefill(params, batch):
            (logits, cache), rep = prefill_p(params, batch,
                                             cache_len=self.cache_len)
            tok = jnp.argmax(logits[..., :cfg.vocab],
                             axis=-1).astype(jnp.int32)
            return tok, cache, rep.as_metrics()

        @jax.jit
        def insert(full, one, slot):
            return jax.tree.map(
                lambda f, o: jax.lax.dynamic_update_slice_in_dim(
                    f, o.astype(f.dtype), slot, axis=1), full, one)

        lane.prefill_fn = prefill
        lane.insert_fn = insert

    # ------------------------------ request payloads -------------------------

    def _chat_tokens(self, req: Request, bucket: int,
                     rng=None) -> np.ndarray:
        """The request's deterministic prompt tokens, padded to ``bucket``.

        A request carrying (prefix_seed, prefix_len) opens with the shared
        system prompt — byte-identical across every request with the same
        prefix seed, which is what the paged prefix tree keys on; the
        suffix (and padding) comes from the request's own seed."""
        cfg = self.cfg
        rng = np.random.default_rng(req.seed) if rng is None else rng
        pfx = min(int(req.prefix_len or 0), bucket)
        if pfx > 0 and req.prefix_seed is not None:
            head = np.random.default_rng(req.prefix_seed).integers(
                0, cfg.vocab, pfx)
            return np.concatenate(
                [head, rng.integers(0, cfg.vocab, bucket - pfx)])
        return rng.integers(0, cfg.vocab, bucket)

    def _chat_batch(self, req: Request) -> dict:
        import jax.numpy as jnp
        cfg = self.cfg
        bucket = self.max_prompt            # single prompt bucket
        rng = np.random.default_rng(req.seed)
        batch = {"tokens": jnp.asarray(
            self._chat_tokens(req, bucket, rng)[None, :], jnp.int32)}
        if cfg.family == "vlm":
            batch["patches"] = jnp.asarray(rng.standard_normal(
                (1, cfg.n_patches, cfg.patch_dim)), jnp.float32)
        if cfg.family == "encdec":
            batch["frames"] = jnp.asarray(rng.standard_normal(
                (1, cfg.enc_seq, cfg.d_model)), jnp.float32)
        return batch

    def _prefill_pos(self) -> int:
        cfg = self.cfg
        pos = self.max_prompt + cfg.meta_tokens
        if cfg.family == "vlm":
            pos += cfg.n_patches
        return pos

    # ------------------------------ warmup -----------------------------------

    def warmup(self, sample: Optional[Request] = None) -> None:
        """Compile every lane's steps outside the telemetry clock.
        ``sample`` pins the dlrm payload shapes (jit traces by shape)."""
        import jax
        import jax.numpy as jnp

        if self._warm:
            return
        dummy = Request(rid=-1, tenant="_warm", arrival_s=0.0,
                        prompt_len=self.max_prompt, max_new_tokens=1,
                        seed=0)
        for lane in self.lanes:
            if self.is_dlrm:
                ex = self.dlrm_extras
                if sample is not None and sample.payload is not None:
                    dense = jnp.zeros(sample.payload["dense"].shape,
                                      jnp.float32)
                    bags = jnp.zeros(sample.payload["bags"].shape,
                                     jnp.int32)
                else:
                    dense = jnp.zeros((1, ex.n_dense), jnp.float32)
                    bags = jnp.zeros((1, sum(ex.pools)) if ex.ragged
                                     else (ex.n_tables, 1, 1), jnp.int32)
                _, metrics = jax.block_until_ready(
                    lane.forward_fn(self.params, dense, bags))
                self._eb_slots[(lane.key, bags.shape)] = int(
                    metrics["abft/eb_slots"])
                continue
            if lane.pager is not None:
                self._warmup_paged(lane, dummy)
                continue
            tok, cache1, _ = lane.prefill_fn(self.params,
                                             self._chat_batch(dummy))
            full = self._widened_cache(cache1, lane.n_slots)
            full = lane.insert_fn(full, cache1, 0)
            toks = jnp.zeros((lane.n_slots,), jnp.int32)
            pos = jnp.full((lane.n_slots,), self._prefill_pos(), jnp.int32)
            jax.block_until_ready(
                lane.decode_fn(self.params, full, toks, pos))
        self._warm = True

    def _warmup_paged(self, lane: _Lane, dummy: Request) -> None:
        """Compile the paged lane's steps against throwaway pool state
        (the allocator/tree are untouched: slot 0's warmup pages live in
        a synthetic table that is discarded afterwards).  Only the
        ``max_prompt`` bucket's prefill/insert compile here; smaller
        buckets compile lazily on first admission."""
        import jax
        import jax.numpy as jnp

        p = self.paging.page_size
        bucket = self._max_bucket
        nc = bucket // p
        batch = {"tokens": jnp.asarray(
            self._chat_tokens(dummy, bucket)[None, :], jnp.int32)}
        tok, cache1, _ = lane.prefill_fn(self.params, batch, bucket)
        if lane.cache is None:
            self._init_paged_cache(lane, cache1)
        tb = np.full((lane.n_slots, lane.pager.max_pages), -1, np.int32)
        tb[0, :nc + 1] = np.arange(nc + 1)
        tdev = jnp.broadcast_to(jnp.asarray(tb),
                                (lane.n_layers,) + tb.shape)
        cache = lane.insert_fn(lane.cache, cache1,
                               jnp.arange(nc, dtype=jnp.int32), tdev)
        cache = lane.reset_fn(cache, self._reset_vec(lane, [nc]))
        toks = jnp.zeros((lane.n_slots,), jnp.int32)
        pos = jnp.full((lane.n_slots,), bucket, jnp.int32)
        jax.block_until_ready(
            lane.decode_fn(self.params, cache, toks, pos))
        jax.block_until_ready(lane.scrub_fn(cache, pos))
        jax.block_until_ready(lane.table_fn(cache, tdev))

    @staticmethod
    def _widened_cache(cache1, n_slots: int):
        import jax
        import jax.numpy as jnp
        return jax.tree.map(
            lambda x: jnp.zeros((x.shape[0], n_slots) + x.shape[2:],
                                x.dtype), cache1)

    # ------------------------------ paged-KV state ---------------------------

    def _init_paged_cache(self, lane: _Lane, cache1) -> None:
        """Size the lane's page pools from the first prefill's cache
        shapes and zero the decode-side state."""
        import jax.numpy as jnp

        from repro.core import QuantKV
        from repro.paging import paged_pool

        if set(cache1) != {"attn"}:
            raise ValueError(f"paged KV expects an attention-only cache; "
                             f"got entries {sorted(cache1)}")
        leaf = cache1["attn"]["k"]
        arr = leaf.q if isinstance(leaf, QuantKV) else leaf
        ell, _, kvh, _, dh = arr.shape
        lane.n_layers = ell
        pg = self.paging
        pool = paged_pool(pg.n_pages, kvh, pg.page_size, dh,
                          lane.n_slots, lane.pager.max_pages, n_layers=ell)
        lane.cache = {"attn": {"k": pool, "v": pool}}
        lane.tokens = jnp.zeros((lane.n_slots,), jnp.int32)
        lane.pos = jnp.zeros((lane.n_slots,), jnp.int32)

    def _table_dev(self, lane: _Lane):
        """The manager's host table broadcast to the stacked-layer shape
        (one page id names the same pool row in every layer)."""
        import jax.numpy as jnp
        t = jnp.asarray(lane.pager.table)
        return jnp.broadcast_to(t, (lane.n_layers,) + t.shape)

    def _reset_vec(self, lane: _Lane, page_ids):
        """Fixed-length page-id vector (sentinel-padded) so reset_pages
        compiles once regardless of how many pages need zeroing."""
        import jax.numpy as jnp
        vec = np.full((lane.n_slots,), self.paging.n_pages, np.int32)
        vec[:len(page_ids)] = page_ids
        return jnp.asarray(vec)

    def _bucket_of(self, req: Request) -> int:
        p = self.paging.page_size
        return min(self._max_bucket, -(-max(int(req.prompt_len), 1) // p) * p)

    def _abort_slot(self, lane: _Lane, slot: Slot, telemetry: Telemetry):
        """Fail ONE request (pool exhausted / unrebuildable page) and free
        its slot + pages; the lane keeps serving."""
        lane.pager.retire(slot.index)
        lane.batcher.retire(slot.index)
        self._record_slot(slot, telemetry, aborted=True)

    def _publish_paging(self, lane: _Lane) -> None:
        if self._obs is None or lane.pager is None:
            return
        st = lane.pager.stats()
        g = self._obs.registry.gauge
        g("repro_paging_pages_resident",
          "allocated pages in the lane pool").set(
              st["pages_resident"], lane=lane.key)
        g("repro_paging_pages_free", "free pages in the lane pool").set(
            st["pages_free"], lane=lane.key)
        g("repro_paging_pages_shared",
          "pages referenced by more than one holder").set(
              st["pages_shared"], lane=lane.key)
        g("repro_paging_pages_high_water",
          "peak allocated pages since reset").set(
              st["pages_high_water"], lane=lane.key)
        g("repro_paging_prefix_hit_rate",
          "prompt chunks served from shared pages").set(
              st["prefix_hit_rate"], lane=lane.key)
        g("repro_paging_page_evictions",
          "pages evicted (LRU pressure + corrupt)").set(
              st["page_evictions"], lane=lane.key)
        g("repro_paging_page_rebuilds",
          "prompt re-prefills after corrupt-page eviction").set(
              st["page_rebuilds"], lane=lane.key)

    def _paging_event(self, action: str, lane: _Lane, *,
                      dur_s: float = 0.0, **attrs) -> None:
        """One paged-KV lifecycle operation (admit / evict_corrupt /
        rebuild / scrub_cache): a tracer span, a
        ``repro_paging_ops_total{action,lane}`` inc, and a typed
        ``info``/``channel=paging`` event — so page-fault response is
        visible in Chrome traces and replayable from the JSONL."""
        if self._obs is None:
            return
        from repro.obs import FaultEvent
        self._obs.tracer.add_span(
            f"paged_{action}", cat="paging",
            start_s=self._obs.tracer.now_s() - dur_s, dur_s=dur_s,
            lane=lane.key, step=self.global_step,
            clock_s=self.clock_s - dur_s, **attrs)
        self._obs.registry.counter(
            "repro_paging_ops_total",
            "paged-KV lifecycle operations by action and lane").inc(
                1, action=action, lane=lane.key)
        rid = attrs.get("rid")
        self._obs.bus.emit(FaultEvent(
            op=action, step=self.global_step, source="serving.engine",
            kind="info", t_s=self.clock_s,
            request_ids=(int(rid),) if rid is not None else (),
            attrs={"channel": "paging", "action": action,
                   "lane": lane.key, **attrs}))

    def paging_stats(self) -> Dict[str, dict]:
        """Per-lane paging stats + byte accounting (campaign metrics)."""
        from repro.paging import pool_page_bytes
        out = {}
        for lane in self.lanes:
            if lane.pager is None:
                continue
            st = lane.pager.stats()
            if lane.cache is not None:
                attn = lane.cache["attn"]
                per_page = (pool_page_bytes(attn["k"])
                            + pool_page_bytes(attn["v"]))
                st["page_bytes"] = per_page
                st["peak_resident_bytes"] = \
                    st["pages_high_water"] * per_page
            out[lane.key] = st
        return out

    # ------------------------------ fault injection --------------------------

    def _apply_injection(self, inj: FaultInjection, telemetry: Telemetry):
        import jax

        from repro.core.inject import random_bitflip_live, victim_leaf_index

        if inj.target == "kv":
            self._apply_kv_injection(inj, telemetry)
            return
        if inj.target != "weights":
            raise ValueError(f"unknown injection target {inj.target!r}; "
                             f"have ('weights', 'kv')")
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        idx, path = victim_leaf_index(self.params, inj.victim)
        clean = leaves[idx]
        leaves[idx] = random_bitflip_live(jax.random.key(inj.seed), clean,
                                          path)
        self.params = jax.tree_util.tree_unflatten(treedef, leaves)
        self._injection_state.append((idx, clean, inj.persistent))
        telemetry.add_injection(InjectionRecord(
            step=self.global_step, victim=path, clock_s=self.clock_s,
            persistent=inj.persistent))
        if self._obs is not None:
            from repro.obs import FaultEvent
            self._obs.registry.counter(
                "repro_injections_total",
                "injected faults by source").inc(1,
                                                 source="serving.engine")
            self._obs.bus.emit(FaultEvent(
                op=path, step=self.global_step, source="serving.engine",
                kind="injection", t_s=self.clock_s,
                attrs={"persistent": inj.persistent, "seed": inj.seed}))

    def _apply_kv_injection(self, inj: FaultInjection,
                            telemetry: Telemetry) -> bool:
        """Flip one int8 KV payload bit of a resident request's prompt
        region — paged lanes flip inside a mapped prompt page, contiguous
        quantized lanes inside the prompt rows of the victim's slot.  The
        flip is memory-resident (no restore entry): it persists until the
        page is evicted/rebuilt or the cache is dropped.  Returns False
        (and records nothing) when no lane holds flippable state."""
        import jax.numpy as jnp

        from repro.core import QuantKV

        rng = np.random.default_rng(inj.seed)
        lanes = [ln for ln in self.lanes
                 if ln.cache is not None and ln.batcher.occupancy()
                 and not self.is_dlrm]
        if not lanes:
            return False
        lane = lanes[int(rng.integers(len(lanes)))]
        slots = lane.batcher.active_slots()
        slot = slots[int(rng.integers(len(slots)))]
        pool_name = "k" if int(rng.integers(2)) == 0 else "v"
        leaf = lane.cache["attn"][pool_name]
        bit = int(rng.integers(8))
        mask = jnp.int8((1 << bit) if bit < 7 else -128)
        if lane.pager is not None:
            chunks = [c for c in
                      range(lane.pager.prompt_chunks[slot.index])
                      if lane.pager.table[slot.index, c] >= 0]
            if not chunks:
                return False
            chunk = chunks[int(rng.integers(len(chunks)))]
            pid = int(lane.pager.table[slot.index, chunk])
            ell, _, kvh, pgs, dh = leaf.q.shape
            idx = (int(rng.integers(ell)), pid, int(rng.integers(kvh)),
                   int(rng.integers(pgs)), int(rng.integers(dh)))
            victim = (f"kv_page/{pool_name}/page{pid}"
                      f"/l{idx[0]}h{idx[2]}r{idx[3]}d{idx[4]}b{bit}")
        elif isinstance(leaf, QuantKV):
            ell, _, kvh, _, dh = leaf.q.shape
            row = int(rng.integers(min(slot.pos, self.max_prompt)))
            idx = (int(rng.integers(ell)), slot.index,
                   int(rng.integers(kvh)), row, int(rng.integers(dh)))
            victim = (f"kv_row/{pool_name}/slot{slot.index}"
                      f"/l{idx[0]}h{idx[2]}r{row}d{idx[4]}b{bit}")
        else:
            return False                  # bf16 cache: nothing checksummed
        newq = leaf.q.at[idx].set(leaf.q[idx] ^ mask)
        lane.cache = {**lane.cache, "attn": {
            **lane.cache["attn"], pool_name: leaf._replace(q=newq)}}
        telemetry.add_injection(InjectionRecord(
            step=self.global_step, victim=victim, clock_s=self.clock_s,
            persistent=True))
        if self._obs is not None:
            from repro.obs import FaultEvent
            self._obs.registry.counter(
                "repro_injections_total",
                "injected faults by source").inc(1,
                                                 source="serving.engine")
            self._obs.bus.emit(FaultEvent(
                op=victim, step=self.global_step, source="serving.engine",
                kind="injection", t_s=self.clock_s,
                attrs={"persistent": True, "seed": inj.seed,
                       "target": "kv"}))
        return True

    def _restore_injection(self, *, include_persistent: bool = False):
        """Undo applied injections in reverse application order —
        transient ones always, persistent ones only on request
        (:meth:`reset_state`)."""
        import jax
        keep = []
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        for idx, clean, persistent in reversed(self._injection_state):
            if persistent and not include_persistent:
                keep.append((idx, clean, persistent))
                continue
            leaves[idx] = clean
        self.params = jax.tree_util.tree_unflatten(treedef, leaves)
        self._injection_state = list(reversed(keep))

    # ------------------------------ engine steps -----------------------------

    @property
    def _tracer(self):
        """The bound Observability's tracer, else None."""
        return None if self._obs is None else self._obs.tracer

    def _timed(self, fn, *args):
        """Run one jitted step to completion: -> (outputs, wall seconds
        from dispatch until the device finished)."""
        import jax
        tracer = self._tracer
        if tracer is not None:
            self._dispatch_s = tracer.now_s()
        t0 = time.perf_counter()
        with span("serving/dispatch", tracer):
            out = fn(*args)
        with span("serving/wait", tracer):
            out = jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        self.clock_s += dt
        return out, dt

    def _fetch(self, read, x):
        """``read(x)``: one blocking device-to-host read (``int`` of a
        scalar, ``np.asarray`` of an array), counted on the next step
        event's ``host_fetches``."""
        self._fetches += 1
        return read(x)

    def _readback(self, metrics, *outs) -> tuple:
        """A step's blocking reads under one span: each of ``outs``, then
        the FaultReport counters -> (host arrays, (counters, errors))."""
        with span("serving/readback", self._tracer):
            host = tuple(self._fetch(np.asarray, x) for x in outs)
            counters = _counters_of(metrics, self._fetch)
        return host, counters

    def _record_slot(self, slot: Slot, telemetry: Telemetry,
                     aborted: bool = False, logits=None):
        req = slot.request
        telemetry.add_request(RequestRecord(
            rid=req.rid, tenant=req.tenant, kind=req.kind,
            arrival_s=req.arrival_s, admit_s=slot.admit_s,
            first_token_s=slot.first_token_s, finish_s=self.clock_s,
            prompt_len=req.prompt_len, tokens_out=slot.generated,
            queue_wait_s=slot.queue_wait_s, aborted=aborted,
            tokens=getattr(slot, "token_ids", None), logits=logits,
            prefill_tokens=slot.prefill_tokens,
            shared_prefix_tokens=slot.shared_prefix_tokens))

    def _step_event(self, kind: str, lane: _Lane, dt: float, metrics,
                    telemetry: Telemetry, injected: bool = False,
                    errors_override: Optional[int] = None,
                    slot_rids: tuple = (), counters=None):
        """Record one step.  ``counters`` is the (counters, errors) pair a
        caller already read back; otherwise ``metrics`` are read here."""
        if counters is None:
            counters = (({}, 0) if metrics is None
                        else self._readback(metrics)[1])
        counters, errors = counters
        if errors_override is not None:
            errors = errors_override
        fetches, self._fetches = self._fetches, 0
        telemetry.add_step(StepEvent(
            step=self.global_step, t_s=self.clock_s, kind=kind,
            lane=lane.key, duration_s=dt,
            occupancy=lane.batcher.occupancy(),
            queue_depth=self.queue.depth(), counters=counters,
            errors=errors, injected=injected,
            slot_rids=tuple(slot_rids), host_fetches=fetches))
        if self._obs is not None:
            self._obs.tracer.add_span(
                kind, cat="serving", start_s=self._dispatch_s, dur_s=dt,
                lane=lane.key, step=self.global_step,
                occupancy=lane.batcher.occupancy(),
                clock_s=self.clock_s - dt)
            self._obs.registry.counter(
                "repro_steps_total", "engine steps by kind").inc(
                    1, kind=kind, source="serving.engine")
            self._obs.registry.counter(
                "repro_host_fetches_total",
                "blocking device-to-host reads by engine step kind").inc(
                    fetches, kind=kind, source="serving.engine")
            self._obs.registry.histogram(
                "repro_step_duration_ms",
                "engine step wall duration").observe(
                    dt * 1e3, kind=kind)
            if metrics is not None:
                from repro.protect.runtime import observe_metrics
                observe_metrics(
                    metrics, source="serving.engine",
                    step=self.global_step, t_s=self.clock_s,
                    obs=self._obs, request_ids=tuple(slot_rids),
                    attrs={"kind": kind, "lane": lane.key,
                           "duration_ms": dt * 1e3,
                           "tenants": sorted({
                               s.request.tenant
                               for s in lane.batcher.active_slots()})})
        return errors

    def _abort_lane(self, lane: _Lane, telemetry: Telemetry, dt: float,
                    injected: bool, slot_rids: tuple = ()):
        """Policy ``abort`` fired: fail the lane's in-flight requests,
        reset the lane, keep serving."""
        for slot in lane.reset():
            self._record_slot(slot, telemetry, aborted=True)
        self._step_event("decode", lane, dt, None, telemetry,
                         injected=injected, errors_override=1,
                         slot_rids=slot_rids)

    def _do_prefill(self, lane: _Lane, slot: Slot, telemetry: Telemetry,
                    injected: bool):
        from repro.core.policy import is_fault_abort

        if lane.pager is not None:
            self._do_prefill_paged(lane, slot, telemetry, injected)
            return
        req = slot.request
        try:
            (tok, cache1, metrics), dt = self._timed(
                lane.prefill_fn, self.params, self._chat_batch(req))
        except Exception as e:          # noqa: BLE001 - abort policy only
            if not is_fault_abort(e):
                raise
            self.clock_s += 1e-6
            lane.batcher.retire(slot.index)
            self._record_slot(slot, telemetry, aborted=True)
            self._step_event("prefill", lane, 0.0, None, telemetry,
                             injected=injected, errors_override=1,
                             slot_rids=(req.rid,))
            return
        if lane.cache is None:
            import jax.numpy as jnp
            lane.cache = self._widened_cache(cache1, lane.n_slots)
            lane.tokens = jnp.zeros((lane.n_slots,), jnp.int32)
            lane.pos = jnp.zeros((lane.n_slots,), jnp.int32)
        lane.cache = lane.insert_fn(lane.cache, cache1, slot.index)
        lane.tokens = lane.tokens.at[slot.index].set(tok[0])
        lane.pos = lane.pos.at[slot.index].set(self._prefill_pos())
        slot.pos = self._prefill_pos()
        slot.generated = 1
        slot.first_token_s = self.clock_s
        (tok_host,), counters = self._readback(metrics, tok)
        slot.token_ids = [int(tok_host[0])]
        slot.prefill_tokens = self.max_prompt   # full fixed-slot bucket
        self._step_event("prefill", lane, dt, metrics, telemetry,
                         injected=injected, slot_rids=(req.rid,),
                         counters=counters)

    def _do_prefill_paged(self, lane: _Lane, slot: Slot,
                          telemetry: Telemetry, injected: bool):
        """Paged admission: prefix-tree lookup, page-bucketed prefill,
        pack the non-shared pages, allocate the first decode-tail page."""
        import jax.numpy as jnp

        from repro.core.policy import is_fault_abort

        req = slot.request
        pager = lane.pager
        p = self.paging.page_size
        bucket = self._bucket_of(req)
        tokens = self._chat_tokens(req, bucket)
        plan = pager.admit(slot.index, tokens)
        if not plan.ok:                      # pool exhausted: shed it
            lane.batcher.retire(slot.index)
            self._record_slot(slot, telemetry, aborted=True)
            return
        batch = {"tokens": jnp.asarray(tokens[None, :], jnp.int32)}
        try:
            (tok, cache1, metrics), dt = self._timed(
                lane.prefill_fn, self.params, batch, bucket)
        except Exception as e:          # noqa: BLE001 - abort policy only
            if not is_fault_abort(e):
                raise
            self.clock_s += 1e-6
            self._abort_slot(lane, slot, telemetry)
            self._step_event("prefill", lane, 0.0, None, telemetry,
                             injected=injected, errors_override=1,
                             slot_rids=(req.rid,))
            return
        if lane.cache is None:
            self._init_paged_cache(lane, cache1)
        tail = pager.decode_page(slot.index, bucket // p)
        if tail is None:
            self._abort_slot(lane, slot, telemetry)
            return
        lane.cache = lane.insert_fn(lane.cache, cache1,
                                    jnp.asarray(plan.page_ids),
                                    self._table_dev(lane))
        lane.cache = lane.reset_fn(lane.cache, self._reset_vec(lane,
                                                               [tail]))
        lane.tokens = lane.tokens.at[slot.index].set(tok[0])
        lane.pos = lane.pos.at[slot.index].set(bucket)
        slot.pos = bucket
        slot.generated = 1
        slot.first_token_s = self.clock_s
        (tok_host,), counters = self._readback(metrics, tok)
        slot.token_ids = [int(tok_host[0])]
        slot.bucket = bucket
        slot.prefill_tokens, slot.shared_prefix_tokens = plan.tokens(p)
        self._paging_event("admit", lane, slot=slot.index, rid=req.rid,
                           bucket=bucket, pages=len(plan.page_ids),
                           shared_pages=plan.shared_pages,
                           new_pages=plan.new_pages)
        self._step_event("prefill", lane, dt, metrics, telemetry,
                         injected=injected, slot_rids=(req.rid,),
                         counters=counters)
        self._publish_paging(lane)

    def _do_decode(self, lane: _Lane, telemetry: Telemetry,
                   injected: bool):
        from repro.core.policy import is_fault_abort

        if lane.pager is not None:
            self._paged_pre_decode(lane, telemetry)
            if not lane.batcher.occupancy():
                return
        resident = tuple(s.request.rid
                         for s in lane.batcher.active_slots())
        try:
            (tok, cache, metrics), dt = self._timed(
                lane.decode_fn, self.params, lane.cache, lane.tokens,
                lane.pos)
        except Exception as e:          # noqa: BLE001 - abort policy only
            if not is_fault_abort(e):
                raise
            self.clock_s += 1e-6
            self._abort_lane(lane, telemetry, 0.0, injected,
                             slot_rids=resident)
            return
        lane.cache = cache
        lane.tokens = tok
        lane.pos = lane.pos + 1
        (tok_host,), counters = self._readback(metrics, tok)
        for slot in lane.batcher.active_slots():
            slot.generated += 1
            slot.pos += 1
            slot.token_ids.append(int(tok_host[slot.index]))
        errors = self._step_event("decode", lane, dt, metrics, telemetry,
                                  injected=injected, slot_rids=resident,
                                  counters=counters)
        if lane.pager is not None and errors > 0:
            policy = lane.plan.resolve("kv_cache_paged", "attn").policy
            paged = metrics.get("abft/kv_cache_paged_errors")
            if paged is not None and self._fetch(int, paged) > 0 \
                    and policy != "log":
                self._paged_repair(lane, telemetry, policy)
        for slot in lane.batcher.retire_finished():
            if lane.pager is not None:
                lane.pager.retire(slot.index)
            self._record_slot(slot, telemetry)
        self._publish_paging(lane)

    def _paged_pre_decode(self, lane: _Lane, telemetry: Telemetry):
        """Before a paged decode step: allocate decode-tail pages for
        slots crossing a page boundary (aborting the owner if the pool is
        truly full), zero them, and push the current page table."""
        pager = lane.pager
        p = self.paging.page_size
        fresh = []
        for slot in list(lane.batcher.active_slots()):
            chunk = slot.pos // p
            if slot.pos % p == 0 and pager.table[slot.index, chunk] < 0:
                pid = pager.decode_page(slot.index, chunk)
                if pid is None:
                    self._abort_slot(lane, slot, telemetry)
                    continue
                fresh.append(pid)
        if not lane.batcher.occupancy():
            return
        if fresh:
            lane.cache = lane.reset_fn(lane.cache,
                                       self._reset_vec(lane, fresh))
        lane.cache = lane.table_fn(lane.cache, self._table_dev(lane))

    def _paged_repair(self, lane: _Lane, telemetry: Telemetry,
                      policy: str):
        """Detect→act for paged KV, host-side: scrub the pool, map the
        flagged (slot, chunk) pairs to pages, then per the plan policy
        evict + rebuild shared/prompt pages via re-prefill
        (``recompute``/``correct``) or abort the owning request
        (``abort`` — and always for an unrebuildable decode-tail page).
        Only the touched requests pay; the lane keeps serving."""
        pager = lane.pager
        t0 = time.perf_counter()
        flags = lane.scrub_fn(lane.cache, lane.pos)
        bad = (self._fetch(np.asarray, flags["k"])
               + self._fetch(np.asarray, flags["v"]))
        self._paging_event("scrub_cache", lane,
                           dur_s=time.perf_counter() - t0,
                           flagged=int((bad > 0).sum()), policy=policy)
        for slot in list(lane.batcher.active_slots()):
            chunks = [int(c) for c in np.nonzero(bad[slot.index])[0]]
            if not chunks:
                continue
            rebuild = policy != "abort"
            if rebuild:
                for c in chunks:
                    if not pager.evict_corrupt(slot.index, c):
                        rebuild = False      # corrupt decode-tail page
                self._paging_event(
                    "evict_corrupt", lane, slot=slot.index,
                    rid=slot.request.rid, chunks=chunks,
                    rebuildable=rebuild)
            if not (rebuild and self._rebuild_prompt(lane, slot,
                                                     telemetry)):
                self._abort_slot(lane, slot, telemetry)
        if lane.batcher.occupancy():
            lane.cache = lane.table_fn(lane.cache, self._table_dev(lane))

    def _rebuild_prompt(self, lane: _Lane, slot: Slot,
                        telemetry: Telemetry) -> bool:
        """Re-prefill a slot's prompt onto fresh pages after a corrupt
        prompt page was evicted; decode-tail pages (the generated KV)
        survive untouched.  Returns False when the pool cannot hold the
        rebuilt pages or the re-prefill itself aborts."""
        import jax.numpy as jnp

        from repro.core.policy import is_fault_abort

        pager = lane.pager
        req = slot.request
        bucket = slot.bucket
        tokens = self._chat_tokens(req, bucket)
        pager.release_prompt(slot.index)
        plan = pager.readmit(slot.index, tokens)
        if not plan.ok:
            return False
        batch = {"tokens": jnp.asarray(tokens[None, :], jnp.int32)}
        try:
            (_, cache1, metrics), dt = self._timed(
                lane.prefill_fn, self.params, batch, bucket)
        except Exception as e:          # noqa: BLE001 - abort policy only
            if not is_fault_abort(e):
                raise
            self.clock_s += 1e-6
            return False
        lane.cache = lane.insert_fn(lane.cache, cache1,
                                    jnp.asarray(plan.page_ids),
                                    self._table_dev(lane))
        self._paging_event("rebuild", lane, dur_s=dt, slot=slot.index,
                           rid=req.rid, pages=len(plan.page_ids))
        self._step_event("rebuild", lane, dt, metrics, telemetry,
                         slot_rids=(req.rid,))
        return True

    def _do_dlrm(self, lane: _Lane, slot_like: Slot, telemetry: Telemetry,
                 injected: bool):
        import jax.numpy as jnp

        from repro.core.policy import is_fault_abort

        req = slot_like.request
        with span("serving/upload", self._tracer):
            dense = jnp.asarray(req.payload["dense"])
            bags = jnp.asarray(req.payload["bags"])
        aborted = False
        metrics, host_logits, counters, dt = None, None, None, 0.0
        try:
            (logits, metrics), dt = self._timed(
                lane.forward_fn, self.params, dense, bags)
        except Exception as e:          # noqa: BLE001 - abort policy only
            if not is_fault_abort(e):
                raise
            self.clock_s += 1e-6
            aborted = True
        else:
            (host_logits,), counters = self._readback(metrics, logits)
            host_logits = host_logits.tolist()
            key = (lane.key, bags.shape)
            if key not in self._eb_slots:
                self._eb_slots[key] = self._fetch(int,
                                                  metrics["abft/eb_slots"])
            counters[0]["eb_slots"] = self._eb_slots[key]
        slot_like.first_token_s = None if aborted else self.clock_s
        self._record_slot(slot_like, telemetry, aborted=aborted,
                          logits=host_logits)
        self._step_event("dlrm", lane, dt, metrics, telemetry,
                         injected=injected,
                         errors_override=1 if aborted else None,
                         slot_rids=(req.rid,), counters=counters)

    def reset_state(self) -> None:
        """Fresh run state (clock, queue, lanes) with compiled steps kept —
        soak campaigns run a clean and a faulty pass on one engine.  Any
        still-applied (persistent) injected fault is restored."""
        if self._injection_state:
            self._restore_injection(include_persistent=True)
        self.clock_s = 0.0
        self.global_step = 0
        self.queue = AdmissionQueue(max_depth=self.queue.max_depth)
        for lane in self.lanes:
            lane.reset()

    # ------------------------------ monitor responses ------------------------

    def _admits(self, lane: _Lane):
        """The lane's admission predicate, gated by tenant health when a
        monitor is attached (quarantined tenants only pass as recovery
        probes)."""
        if self._monitor is None:
            return lane.accepts
        mon = self._monitor
        return lambda req: (lane.accepts(req)
                            and mon.admission_allowed(req.tenant))

    def _health_action(self, action: str, scope: str,
                       lane: _Lane) -> None:
        """Record one applied engine response (quarantine / escalate /
        scrub / recover) as a counter + typed health event, so the
        response is visible from the JSONL alone."""
        if self._obs is None:
            return
        from repro.obs import FaultEvent
        self._obs.registry.counter(
            "repro_health_actions_total",
            "engine responses to health transitions").inc(
                1, action=action, scope=scope)
        self._obs.bus.emit(FaultEvent(
            op="health", step=self.global_step, source="serving.engine",
            kind="health", t_s=self.clock_s,
            attrs={"scope": scope, "action": action, "lane": lane.key}))

    def _escalate_lane(self, lane: _Lane) -> bool:
        """Upgrade the lane's plan detect→act policies (``log`` →
        ``recompute``) and re-jit its steps; one-way per engine.  The
        escalated plan changes no op enablement, so cache/batch structure
        is stable across the swap."""
        if lane.key in self._escalated:
            return False
        lane.plan = lane.plan.escalated()
        self._build_lane_fns(lane)
        self._escalated.add(lane.key)
        return True

    def _apply_monitor_responses(self, telemetry: Telemetry) -> None:
        """Drain the monitor's health transitions and apply the
        configured responses to the owning tenant lanes."""
        mon = self._monitor
        from repro.obs.health import HEALTH_STATES
        for tr in mon.poll_transitions():
            if not tr.scope.startswith("tenant:"):
                continue
            tenant = tr.scope.split(":", 1)[1]
            lane = self._lane_of.get(tenant)
            if lane is None:
                continue
            worse = (HEALTH_STATES.index(tr.new)
                     > HEALTH_STATES.index(tr.old))
            if not worse:
                self._health_action("recover", tr.scope, lane)
                continue
            if tr.new == "quarantined" and mon.responses.quarantine:
                self._health_action("quarantine", tr.scope, lane)
            if mon.responses.escalate and self._escalate_lane(lane):
                self._health_action("escalate", tr.scope, lane)
            if mon.responses.scrub and lane.pager is not None \
                    and lane.cache is not None:
                self._paged_repair(lane, telemetry, "recompute")
                self._health_action("scrub", tr.scope, lane)

    # ------------------------------ adaptive thresholds ----------------------

    def _register_adaptive(self) -> None:
        """One controller per (op, tenant) whose lane plan opts the op
        into ``threshold=adaptive``, seeded from the plan's resolved
        ``rel_bound`` (or the op default) unless the caller pre-seeded
        the controller (e.g. from ``calibrate_from_sweep``)."""
        from repro.adapt import _op_default_bound
        from repro.core.policy import op_kinds
        for lane in self.lanes:
            for op in op_kinds():
                r = lane.plan.resolve(op)
                if not (r.enabled and r.threshold == "adaptive"):
                    continue
                current = (r.rel_bound if r.rel_bound is not None
                           else _op_default_bound(op))
                for tenant in sorted(lane.tenants):
                    c = self._adapt.manage(op, tenant,
                                           rel_bound=r.rel_bound)
                    # the lane compiles against the controller's bound
                    # (which may predate this run via calibration)
                    if c.rel_bound != current:
                        self._apply_bound(op, tenant, c.rel_bound)

    def _apply_bound(self, op: str, tenant: str, bound: float) -> None:
        """Rewrite one tenant lane's plan with the controller's bound
        and re-jit — the ``_escalate_lane`` precedent.  Hysteresis +
        cooldown keep moves (and hence recompiles) rare."""
        lane = self._lane_of.get(tenant)
        if lane is None:
            return
        from repro.protect.plan import OpRule
        lane.plan = lane.plan.with_rules(
            OpRule(pattern=op, rel_bound=float(bound)))
        self._build_lane_fns(lane)

    def _apply_adaptive(self) -> None:
        if self._adapt is None or self._monitor is None:
            return
        moved = self._adapt.tick(self._monitor, t_s=self.clock_s,
                                 step=self.global_step)
        for (op, tenant), bound in moved.items():
            self._apply_bound(op, tenant, bound)

    # ------------------------------ main loop --------------------------------

    def run(self, requests: Sequence[Request], *,
            inject: Optional[Sequence[FaultInjection]] = None,
            telemetry: Optional[Telemetry] = None,
            warmup: bool = True,
            max_iterations: int = 1_000_000,
            obs=None, monitor=None, adapt=None) -> Telemetry:
        """Serve ``requests`` to completion.  ``obs`` (an
        :class:`repro.obs.Observability`) additionally lands every step's
        FaultReport counters, spans, and per-request-attributed detection
        events host-side for the duration of this run.

        ``monitor`` (a :class:`repro.obs.Monitor`) closes the loop: it is
        bound to ``obs`` (one is created if the caller passed none), fed
        by the engine's step summaries over the bus, and its health
        transitions trigger real responses between iterations — gate a
        quarantined tenant's admissions (with recovery probes), escalate
        the lane's ProtectionPlan (``log`` → ``recompute``), and schedule
        a paged-KV scrub+repair.  The monitor's summary lands on the
        returned telemetry.

        ``adapt`` (a :class:`repro.adapt.AdaptiveThresholds`) closes the
        *threshold* loop on top of the monitor: lanes whose plan marks
        an op ``threshold=adaptive`` get one FP-budget controller per
        (op, tenant) which reads the monitor's Wilson flag-rate estimate
        each iteration and rewrites the lane's ``rel_bound`` (plan
        rewrite + re-jit) when it moves; requires ``monitor``.

        The call is one ``serving/run`` span (recorded in ``obs``'s tracer
        too), holding the steps' ``serving/*`` spans."""
        if adapt is not None and monitor is None:
            raise ValueError("adapt= needs monitor= (its sensor)")
        if monitor is not None and obs is None:
            from repro.obs import Observability
            obs = Observability.create()
        with span("serving/run", None if obs is None else obs.tracer):
            telemetry = telemetry if telemetry is not None else Telemetry()
            self._fetches = 0
            self._obs = obs
            self._monitor = monitor
            if monitor is not None:
                monitor.bind(obs)
            self._adapt = adapt
            if adapt is not None:
                adapt.bind(obs)
                self._register_adaptive()
            pending = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
            for r in pending:
                if r.tenant not in self._lane_of:
                    raise ValueError(f"request {r.rid} names unknown tenant "
                                     f"{r.tenant!r}; have "
                                     f"{sorted(self._lane_of)}")
            injections = sorted(inject or [], key=lambda i: i.step)
            inj_i = 0
            if warmup:
                self.warmup(pending[0] if pending else None)

            try:
                out = self._run_loop(pending, injections, inj_i, telemetry,
                                     max_iterations)
                if monitor is not None:
                    out.monitor = monitor.summary()
                if adapt is not None:
                    out.thresholds = adapt.summary()
                return out
            finally:
                self._obs = None
                self._monitor = None
                self._adapt = None

    def _run_loop(self, pending, injections, inj_i, telemetry,
                  max_iterations) -> Telemetry:
        i = 0
        it = 0
        while True:
            it += 1
            if it > max_iterations:
                raise RuntimeError("engine exceeded max_iterations "
                                   "(stuck request stream?)")
            # 1. arrivals whose time has come; a full bounded queue sheds
            #    load — the rejection IS the SLO story, so it is recorded
            while i < len(pending) and pending[i].arrival_s <= self.clock_s:
                req = pending[i]
                if not self.queue.push(req, self.clock_s):
                    telemetry.add_request(RequestRecord(
                        rid=req.rid, tenant=req.tenant, kind=req.kind,
                        arrival_s=req.arrival_s, admit_s=self.clock_s,
                        first_token_s=None, finish_s=self.clock_s,
                        prompt_len=req.prompt_len, tokens_out=0,
                        queue_wait_s=0.0, aborted=True, rejected=True))
                i += 1
            active = any(lane.batcher.occupancy() for lane in self.lanes)
            if not self.queue and not active:
                if i >= len(pending):
                    break
                # idle: jump the virtual clock to the next arrival
                self.clock_s = max(self.clock_s, pending[i].arrival_s)
                continue

            injected_now = (inj_i < len(injections)
                            and injections[inj_i].step <= self.global_step)
            if injected_now:
                self._apply_injection(injections[inj_i], telemetry)
                inj_i += 1

            clock_before = self.clock_s
            # 2. admissions + prefills (or one-shot dlrm execution) —
            #    a quarantined tenant's requests stay queued, except for
            #    the monitor's periodic recovery probes
            for lane in self.lanes:
                for slot in lane.batcher.admit(self.queue, self.clock_s,
                                               accept=self._admits(lane)):
                    if slot.request.kind == "dlrm":
                        lane.batcher.retire(slot.index)
                        self._do_dlrm(lane, slot, telemetry, injected_now)
                    else:
                        self._do_prefill(lane, slot, telemetry,
                                         injected_now)
                for slot in lane.batcher.retire_finished():
                    if lane.pager is not None:
                        lane.pager.retire(slot.index)
                    self._record_slot(slot, telemetry)

            # 3. one decode step per lane with active slots
            for lane in self.lanes:
                if lane.batcher.occupancy():
                    self._do_decode(lane, telemetry, injected_now)

            if self._monitor is not None:
                if self.clock_s == clock_before and (
                        self.queue or any(l.batcher.occupancy()
                                          for l in self.lanes)):
                    # fully gated iteration: nothing stepped, so nothing
                    # ticked the monitor — advance the clock a hair and
                    # tick it manually so recovery/probes can unlock
                    self.clock_s += 1e-3
                    self._monitor.idle_tick(self.clock_s)
                self._apply_monitor_responses(telemetry)
                self._apply_adaptive()

            if injected_now:
                self._restore_injection()
            self.global_step += 1

        telemetry.finalize_injections()
        return telemetry


__all__ = ["ServingEngine", "TenantSpec", "FaultInjection",
           "tenant_weights"]
