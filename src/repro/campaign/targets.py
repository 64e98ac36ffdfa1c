"""Registry of injectable targets.

A target owns the three pure functions a campaign cell needs:

* ``build(plan, key)``  — materialize the operand state for one cell
  (tables, weights, precomputed checksums, model params...);
* ``trial(state, plan, key)`` — inject one fault, run the protected op,
  return ``(detected, corrupted)`` booleans.  ``corrupted`` is the target's
  ground truth for "did the fault matter" (bits changed for operator
  targets; observable output changed for the full-model soak), which is
  what separates *masked* faults from *SDC escapes* in the metrics;
* ``clean(state, plan, key)`` — run fault-free, return the (false-positive)
  flag.

All three are jit/vmap-safe; the executor vmaps ``trial``/``clean`` over
key batches and pmaps the batches across host devices.  ``overhead``
optionally returns (protected, unprotected) thunks the executor times to
produce the per-cell overhead column.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.campaign.spec import CellPlan, DLRM_GEMM_SHAPES
from repro.core import abft_gemm as ag
from repro.core import abft_kvcache as kv
from repro.core.inject import (bit_band, random_bitflip,
                               random_bitflip_live, random_bitflips,
                               random_value, victim_leaf_index)
from repro.protect.ops import EMBEDDING_BAG, KV_CACHE, QGEMM
from repro.protect.plan import ResolvedRule


def apply_fault(key: jax.Array, x: jax.Array, plan: CellPlan,
                path: str = "") -> jax.Array:
    """The spec'd fault model applied to one array.  ``path`` (the victim
    leaf's dotted path) lets single bit flips avoid the dead alignment
    lanes of packed weights (:func:`repro.core.inject.random_bitflip_live`)
    so victim sweeps measure live faults, not guaranteed-masked ones."""
    if plan.fault_model == "bitflip":
        rng = bit_band(x.dtype, plan.bit_band)
        if plan.flips == 1:
            return random_bitflip_live(key, x, path, bit_range=rng)
        return random_bitflips(key, x, plan.flips, bit_range=rng)
    if plan.fault_model == "random_value":
        return random_value(key, x)
    raise ValueError(f"unknown fault model {plan.fault_model!r}")


@dataclasses.dataclass(frozen=True)
class InjectableTarget:
    name: str
    build: Callable[[CellPlan, jax.Array], Any]
    #: single-shot trial (exactly one of ``trial`` / ``soak`` must be set)
    trial: Optional[Callable[[Any, CellPlan, jax.Array],
                             Tuple[jax.Array, jax.Array]]] = None
    clean: Optional[Callable[[Any, CellPlan, jax.Array], jax.Array]] = None
    default_shapes: Tuple[Tuple[int, ...], ...] = ()
    shape_arity: int = 0
    dtypes: Tuple[str, ...] = ("int8",)
    fault_models: Tuple[str, ...] = ("bitflip", "random_value")
    bands: Tuple[str, ...] = ("all", "low", "significant", "sign")
    analytic_bound: Callable[[CellPlan], Optional[float]] = lambda p: None
    overhead: Optional[Callable[[Any, CellPlan],
                                Tuple[Callable, Callable]]] = None
    #: optional named phase thunks ({"encode": fn, "gemm": fn, ...}) the
    #: executor times individually into the artifact's
    #: ``overhead_breakdown`` column (measure_overhead cells only)
    overhead_phases: Optional[Callable[[Any, CellPlan],
                                       dict]] = None
    #: False for targets whose trial injects into a single element —
    #: expand() skips flips_per_trial > 1 plans for them
    multi_flip: bool = True
    #: True for targets with a tunable detection threshold (the EB
    #: rel_bound) — expand() sweeps spec.rel_bounds over them only
    thresholded: bool = False
    #: True for targets whose injection victim is addressable by leaf-path
    #: pattern (protect-plan vocabulary) — expand() sweeps spec.victims
    #: over them only
    victim_selectable: bool = False
    #: multi-step soak protocol (replaces ``trial`` when set): one call =
    #: ``plan.steps`` consecutive steps with the fault injected at step 0
    #: (re-struck every step when ``plan.persistent``).  Must return a dict
    #: of fixed-shape arrays: ``detected_steps`` (bool [steps]),
    #: ``corrupted`` (bool), ``divergence`` / ``loss_divergence`` (f32
    #: scalars vs the clean twin run).  expand() routes spec.steps /
    #: spec.persistent sweeps to these targets only.
    soak: Optional[Callable[[Any, CellPlan, jax.Array], dict]] = None
    #: True for soak targets that can run under a data-shard mesh: their
    #: ``build`` accepts a ``mesh=`` kwarg and their soak executes the
    #: collective through ``shard_map`` when ``plan.data_shards > 1`` —
    #: expand() routes spec.mesh sweeps to these targets only.  Sharded
    #: soaks additionally return ``shard_detected`` (bool [shards]) for
    #: the per-shard FaultReport merge.
    shardable: bool = False

    def __post_init__(self):
        if (self.trial is None) == (self.soak is None):
            raise ValueError(
                f"target {self.name!r}: exactly one of trial/soak required")
        if self.clean is None:
            raise ValueError(f"target {self.name!r}: clean is required")


TARGETS: dict = {}


def register_target(target: InjectableTarget) -> InjectableTarget:
    TARGETS[target.name] = target
    return target


def get_target(name: str) -> InjectableTarget:
    if name not in TARGETS:
        raise KeyError(
            f"unknown target {name!r}; registered: {sorted(TARGETS)}")
    return TARGETS[name]


# ---------------------------------------------------------------------------
# GEMM targets — paper Table II.  Serving model: B's checksum is encoded
# once from the CLEAN weights; the injected flip is a memory error the
# amortized checksum must catch (§IV-A1).
# ---------------------------------------------------------------------------

def _gemm_build(plan: CellPlan, key: jax.Array):
    m, n, k = plan.shape
    ka, kb = jax.random.split(key)
    a = jax.random.randint(ka, (m, k), 0, 256, jnp.uint8)
    b = jax.random.randint(kb, (k, n), -127, 128, jnp.int8)
    # serving memory model: checksum lanes encoded ONCE from clean weights
    packed = QGEMM.encode(b)
    return {"a": a, "b": b, "lanes": packed[:, b.shape[1]:],
            "checksum": ag.encode_weight_checksum(b)}


def _gemm_repack(state, b_bad):
    """B' with the (clean, amortized) checksum lanes riding along."""
    return jnp.concatenate([b_bad, state["lanes"]], axis=1)


def _gemm_b_trial(state, plan: CellPlan, key: jax.Array):
    b_bad = apply_fault(key, state["b"], plan)
    _, check = QGEMM(_gemm_repack(state, b_bad), state["a"])
    return check.err_count > 0, jnp.any(b_bad != state["b"])


def _gemm_clean(state, plan: CellPlan, key: jax.Array):
    del key
    _, check = QGEMM(_gemm_repack(state, state["b"]), state["a"])
    return check.err_count > 0


def _gemm_bound(plan: CellPlan):
    m = plan.shape[0]
    if plan.fault_model == "bitflip" and plan.flips == 1 \
            and plan.bit_band == "all":
        return ag.detect_prob_b_bitflip(m)
    if plan.fault_model == "random_value":
        return ag.detect_prob_b_random(m)
    return None


def _gemm_overhead(state, plan: CellPlan):
    a = state["a"]
    b_packed = _gemm_repack(state, state["b"])

    def protected():
        return QGEMM(b_packed, a)[0]

    def unprotected():
        return QGEMM.unprotected(b_packed, a)

    return protected, unprotected


def _gemm_phases(state, plan: CellPlan) -> dict:
    """encode / gemm / verify — §IV's amortization story as numbers: the
    encode phase is the amortized one-time cost, gemm the baseline, and
    verify the per-call detection surcharge."""
    a, b = state["a"], state["b"]
    b_packed = _gemm_repack(state, b)
    n = b.shape[1]
    c_full = jax.lax.dot_general(
        a, b_packed, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    c, check_col = c_full[:, :n], c_full[:, n]
    return {
        "encode": lambda: QGEMM.encode(b),
        "gemm": lambda: QGEMM.unprotected(b_packed, a),
        "verify": lambda: ag.verify_rows(c, check_col),
    }


register_target(InjectableTarget(
    name="gemm_packed",
    build=_gemm_build, trial=_gemm_b_trial, clean=_gemm_clean,
    default_shapes=((20, 256, 512),), shape_arity=3,
    analytic_bound=_gemm_bound, overhead=_gemm_overhead,
    overhead_phases=_gemm_phases))


# Fused Pallas implementation as a measured scheme: identical build (so
# cells differ only in execution path), trials routed through
# scheme="pallas" — interpret mode on CPU, the real kernel on TPU.  The
# --grid pallas campaign runs this next to gemm_packed (the XLA reference
# off the TPU) on the same flip grid and gates on detection parity
# (overlapping Wilson CIs).

_PALLAS = ResolvedRule(scheme="pallas")


def _gemm_pallas_trial(state, plan: CellPlan, key: jax.Array):
    b_bad = apply_fault(key, state["b"], plan)
    _, check = QGEMM(_gemm_repack(state, b_bad), state["a"], rule=_PALLAS)
    return check.err_count > 0, jnp.any(b_bad != state["b"])


def _gemm_pallas_clean(state, plan: CellPlan, key: jax.Array):
    del key
    _, check = QGEMM(_gemm_repack(state, state["b"]), state["a"],
                     rule=_PALLAS)
    return check.err_count > 0


def _gemm_pallas_overhead(state, plan: CellPlan):
    a = state["a"]
    b_packed = _gemm_repack(state, state["b"])

    def protected():
        return QGEMM(b_packed, a, rule=_PALLAS)[0]

    def unprotected():
        return QGEMM.unprotected(b_packed, a)

    return protected, unprotected


def _gemm_pallas_phases(state, plan: CellPlan) -> dict:
    """encode / gemm / fused_gemm_verify — the fused kernel has no separate
    verify phase by construction (the epilogue checks the tile the MXU just
    produced), so the breakdown times the whole fused call instead and the
    surcharge is fused_gemm_verify − gemm."""
    a, b = state["a"], state["b"]
    b_packed = _gemm_repack(state, b)
    return {
        "encode": lambda: QGEMM.encode(b),
        "gemm": lambda: QGEMM.unprotected(b_packed, a),
        "fused_gemm_verify": lambda: QGEMM(b_packed, a, rule=_PALLAS)[0],
    }


register_target(InjectableTarget(
    name="gemm_pallas",
    build=_gemm_build, trial=_gemm_pallas_trial, clean=_gemm_pallas_clean,
    default_shapes=((20, 256, 512),), shape_arity=3,
    analytic_bound=_gemm_bound, overhead=_gemm_pallas_overhead,
    overhead_phases=_gemm_pallas_phases))


def _gemm_c_build(plan: CellPlan, key: jax.Array):
    """Precompute the clean int32 C and its checksum column once per cell;
    trials corrupt C (the accumulator-resident intermediate, §IV-C2)."""
    m, n, k = plan.shape
    st = _gemm_build(plan, key)
    b_packed = ag.pack_encoded_b(st["b"], st["checksum"])
    c_full = jax.lax.dot_general(
        st["a"], b_packed, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return {"c": c_full[:, :n], "check_col": c_full[:, n]}


def _gemm_c_trial(state, plan: CellPlan, key: jax.Array):
    c_bad = apply_fault(key, state["c"], plan)
    _, err = ag.verify_rows(c_bad, state["check_col"])
    return err > 0, jnp.any(c_bad != state["c"])


def _gemm_c_clean(state, plan: CellPlan, key: jax.Array):
    del key
    _, err = ag.verify_rows(state["c"], state["check_col"])
    return err > 0


def _gemm_c_bound(plan: CellPlan):
    if plan.fault_model == "bitflip":
        return 1.0          # 2^k mod 127 != 0 for every k: always caught
    return ag.detect_prob_c_random()


register_target(InjectableTarget(
    name="gemm_c",
    build=_gemm_c_build, trial=_gemm_c_trial, clean=_gemm_c_clean,
    default_shapes=((20, 256, 512),), shape_arity=3,
    dtypes=("int32",), analytic_bound=_gemm_c_bound))


# ---------------------------------------------------------------------------
# EmbeddingBag target — paper Table III.  The flip strikes a random element
# among the rows a bag accesses (an untouched-row flip is invisible by
# construction).  α/β follow the trained-table regime of
# benchmarks/eb_detection.py: α ~ U(0.01, 0.02), β ~ U(0.3, 0.7), so the
# low-bit band straddles the round-off bound exactly as in the paper.
# ---------------------------------------------------------------------------

def _eb_build(plan: CellPlan, key: jax.Array):
    rows, dim, _, _ = plan.shape
    kt, ka, kb = jax.random.split(key, 3)
    table = jax.random.randint(kt, (rows, dim), -128, 128, jnp.int8)
    alphas = jax.random.uniform(ka, (rows,), jnp.float32, 1e-2, 2e-2)
    betas = jax.random.uniform(kb, (rows,), jnp.float32, 0.3, 0.7)
    return {"table": table, "alphas": alphas, "betas": betas,
            "rowsums": EMBEDDING_BAG.encode((table, alphas, betas))[-1]}


def _eb_rule(plan: CellPlan) -> ResolvedRule:
    """The cell's Eq. (5) threshold as a plan rule (None = default)."""
    return ResolvedRule(rel_bound=plan.rel_bound)


def _eb_rule_pallas(plan: CellPlan) -> ResolvedRule:
    """Same threshold, forced through the fused Pallas kernel — ONE trial
    body serves both EB targets (rule_fn partial below), so the flip grid
    and the Eq. (5) semantics cannot drift between the measured paths."""
    return ResolvedRule(rel_bound=plan.rel_bound, scheme="pallas")


def _eb_enc(state):
    return (state["table"], state["alphas"], state["betas"],
            state["rowsums"])


def _eb_trial(state, plan: CellPlan, key: jax.Array, rule_fn=_eb_rule):
    rows, dim, bags, pool = plan.shape
    table = state["table"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    idx = jax.random.randint(k1, (bags, pool), 0, rows, jnp.int32)
    # distinct keys per victim coordinate: reusing k2 for both draws
    # made (b, p) perfectly correlated quantiles, so sweep points within
    # a bit band sampled a 1-D slice of the victim space
    b = jax.random.randint(k2, (), 0, bags)
    p = jax.random.randint(jax.random.fold_in(k2, 1), (), 0, pool)
    row = idx[b, p]
    col = jax.random.randint(k3, (), 0, dim)
    elem = table[row, col]
    bad = apply_fault(k4, elem[None], plan)[0]
    table_bad = table.at[row, col].set(bad)
    _, check = EMBEDDING_BAG(
        (table_bad, state["alphas"], state["betas"], state["rowsums"]),
        idx, rule=rule_fn(plan))
    return check.err_count > 0, bad != elem


def _eb_clean(state, plan: CellPlan, key: jax.Array, rule_fn=_eb_rule):
    rows, dim, bags, pool = plan.shape
    idx = jax.random.randint(key, (bags, pool), 0, rows, jnp.int32)
    _, check = EMBEDDING_BAG(_eb_enc(state), idx, rule=rule_fn(plan))
    return check.err_count > 0


def _eb_overhead(state, plan: CellPlan, rule_fn=_eb_rule):
    rows, dim, bags, pool = plan.shape
    idx = jax.random.randint(jax.random.key(0), (bags, pool), 0, rows,
                             jnp.int32)
    enc, rule = _eb_enc(state), rule_fn(plan)

    def protected():
        return EMBEDDING_BAG(enc, idx, rule=rule)[0]

    def unprotected():
        return EMBEDDING_BAG.unprotected(enc, idx)

    return protected, unprotected


def _eb_phases(state, plan: CellPlan, rule_fn=_eb_rule) -> dict:
    rows, dim, bags, pool = plan.shape
    idx = jax.random.randint(jax.random.key(0), (bags, pool), 0, rows,
                             jnp.int32)
    enc, rule = _eb_enc(state), rule_fn(plan)
    return {
        "encode": lambda: EMBEDDING_BAG.encode(
            (state["table"], state["alphas"], state["betas"])),
        "lookup": lambda: EMBEDDING_BAG.unprotected(enc, idx),
        "lookup_verify": lambda: EMBEDDING_BAG(enc, idx, rule=rule)[0],
    }


register_target(InjectableTarget(
    name="embedding_bag",
    build=_eb_build, trial=_eb_trial, clean=_eb_clean,
    default_shapes=((10_000, 128, 10, 100),), shape_arity=4,
    overhead=_eb_overhead, overhead_phases=_eb_phases,
    multi_flip=False, thresholded=True))


# the fused EB kernel vmaps in interpret mode but at ~CPU-emulation speed,
# so the default cell is smaller than embedding_bag's; the pallas grid pins
# BOTH EB targets to this shape so their cells stay directly comparable
register_target(InjectableTarget(
    name="eb_pallas",
    build=_eb_build,
    trial=functools.partial(_eb_trial, rule_fn=_eb_rule_pallas),
    clean=functools.partial(_eb_clean, rule_fn=_eb_rule_pallas),
    default_shapes=((2000, 64, 8, 32),), shape_arity=4,
    overhead=functools.partial(_eb_overhead, rule_fn=_eb_rule_pallas),
    overhead_phases=functools.partial(_eb_phases,
                                      rule_fn=_eb_rule_pallas),
    multi_flip=False, thresholded=True))


# ---------------------------------------------------------------------------
# KV-cache target (beyond-paper: core.abft_kvcache).  dtype selects the
# victim: int8 = the quantized cache payload (exact integer checksum — the
# detector's home turf), float32 = the α dequant scales, which the rowsum
# does NOT cover — a deliberate coverage-gap cell whose escape rate
# quantifies what an attacker of the scales gets away with.
# ---------------------------------------------------------------------------

def _kv_build(plan: CellPlan, key: jax.Array):
    b, heads, s, dh = plan.shape
    x = jax.random.normal(key, (b, heads, s, dh), jnp.float32)
    return {"kv": KV_CACHE.encode(x), "x": x}


def _kv_trial(state, plan: CellPlan, key: jax.Array):
    q = state["kv"]
    if plan.dtype == "float32":
        alpha_bad = apply_fault(key, q.alpha, plan)
        bad = kv.QuantKV(q.q, alpha_bad, q.beta, q.rowsum)
        changed = jnp.any(alpha_bad != q.alpha)
    else:
        q_bad = apply_fault(key, q.q, plan)
        bad = kv.QuantKV(q_bad, q.alpha, q.beta, q.rowsum)
        changed = jnp.any(q_bad != q.q)
    _, err = kv.verify_kv(bad)
    return err > 0, changed


def _kv_clean(state, plan: CellPlan, key: jax.Array):
    del key
    _, err = kv.verify_kv(state["kv"])
    return err > 0


def _kv_bound(plan: CellPlan):
    if plan.dtype == "int8" and plan.fault_model == "bitflip":
        return 1.0          # exact integer rowsum: any payload flip caught
    if plan.dtype == "float32":
        return 0.0          # scales are outside the checksum: by design
    return None


def _kv_overhead(state, plan: CellPlan):
    q = state["kv"]

    def protected():
        _, err = kv.verify_kv(q)
        return KV_CACHE.dequantize(q), err

    def unprotected():
        return KV_CACHE.dequantize(q)

    return protected, unprotected


def _kv_phases(state, plan: CellPlan) -> dict:
    q = state["kv"]
    return {
        "quantize": lambda: KV_CACHE.encode(state["x"]),
        "verify": lambda: kv.verify_kv(q),
        "dequantize": lambda: KV_CACHE.dequantize(q),
    }


register_target(InjectableTarget(
    name="kv_cache",
    build=_kv_build, trial=_kv_trial, clean=_kv_clean,
    default_shapes=((2, 2, 128, 64),), shape_arity=4,
    dtypes=("int8", "float32"),
    bands=("all", "low", "significant", "sign", "exponent", "mantissa",
           "high_mantissa"),
    analytic_bound=_kv_bound, overhead=_kv_overhead,
    overhead_phases=_kv_phases))


# ---------------------------------------------------------------------------
# Full-model decode soak (launch.steps + a reduced registry arch).
# One trial = flip bits in a weight leaf, scan ``plan.steps`` consecutive
# decode steps (fault struck at step 0; re-struck every step when
# ``plan.persistent``), read each step's ABFT counters.  ``corrupted`` is
# the OBSERVABLE output change (any generated token differs from the
# clean twin sequence), so the cell's categories line up with the
# fault-injection literature: detected / masked / SDC escape — and the
# soak protocol gives persistent weight faults the same per-step
# detection-latency histograms the training targets report.  At steps=1
# this is bit-identical to the legacy single-shot trial (same key, same
# flip, one decode), so the committed quick baseline stays valid.
# ---------------------------------------------------------------------------

DECODE_ARCH = "llama3.2-1b"


def _decode_build(plan: CellPlan, key: jax.Array):
    import numpy as np

    from repro.configs import reduce_cfg
    from repro.configs.registry import get_arch
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.layers.common import Ctx
    from repro.models.base import build_model
    from repro.protect import default_plan, unprotected_plan
    from repro.sharding import values_of

    batch, prompt_len = plan.shape
    cfg = reduce_cfg(get_arch(DECODE_ARCH))
    cache_len = prompt_len + cfg.meta_tokens + 8
    model = build_model(cfg, max_pos=cache_len + 8)
    ctx = Ctx(quant=True, plan=default_plan(),
              compute_dtype=jnp.bfloat16)
    params = values_of(
        jax.jit(lambda k: model.init(k, quant=True))(key))

    rng = np.random.default_rng(plan.seed)
    batch_in = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (batch, prompt_len)), jnp.int32)}
    prefill = jax.jit(make_prefill_step(model, ctx, cache_len=cache_len))
    tok, cache, _ = prefill(params, batch_in)
    pos = jnp.full((batch,), prompt_len + cfg.meta_tokens, jnp.int32)

    decode = make_decode_step(model, ctx)

    # the clean twin: plan.steps greedy decode steps from the prefill
    # state — the soak's per-step SDC ground truth (deterministic decode)
    def _clean_scan(carry, _):
        c_cache, c_tok, c_pos = carry
        t2, c2, _ = decode(params, c_cache, c_tok, c_pos)
        return (c2, t2, c_pos + 1), t2

    (_, clean_toks) = jax.lax.scan(
        _clean_scan, (cache, tok, pos), None, length=plan.steps)
    clean_toks = jax.block_until_ready(clean_toks)      # [steps, batch]

    # victim: addressed by the plan's leaf-path pattern in the protect
    # vocabulary (``attn.wq``, ``mlp.down``, ``embed.table``, ...); the
    # default (None) keeps the legacy choice — largest int8 leaf
    leaves, treedef = jax.tree_util.tree_flatten(params)
    victim_idx, victim_path = victim_leaf_index(params, plan.victim)

    state = {"leaves": leaves, "treedef": treedef,
             "victim_idx": victim_idx, "victim_path": victim_path,
             "cache": cache, "tok": tok,
             "pos": pos, "decode": decode, "clean_toks": clean_toks}
    if plan.measure_overhead:
        ctx_off = Ctx(quant=True, plan=unprotected_plan(),
                      compute_dtype=jnp.bfloat16)
        state["decode_off"] = make_decode_step(model, ctx_off)
        state["params"] = params
    return state


def _decode_soak(state, plan: CellPlan, key: jax.Array):
    victim = state["leaves"][state["victim_idx"]]
    # the flip is computed ONCE from the trial key (exactly the legacy
    # single-shot fault) and gated per step with a where-mask, so the
    # scan body stays shape-static under vmap
    bad = apply_fault(key, victim, plan, path=state["victim_path"])
    strike = jnp.ones((plan.steps,), bool) if plan.persistent \
        else (jnp.arange(plan.steps) == 0)

    def body(carry, do_strike):
        cache, tok, pos = carry
        leaves = list(state["leaves"])
        leaves[state["victim_idx"]] = jnp.where(do_strike, bad, victim)
        params = jax.tree_util.tree_unflatten(state["treedef"], leaves)
        tok2, cache2, metrics = state["decode"](params, cache, tok, pos)
        errs = metrics.get("abft/qgemm_errors", 0) \
            + metrics.get("abft/embedding_bag_errors", 0) \
            + metrics.get("abft/kv_cache_errors", 0)
        return (cache2, tok2, pos + 1), (jnp.asarray(errs) > 0, tok2)

    _, (det_steps, toks) = jax.lax.scan(
        body, (state["cache"], state["tok"], state["pos"]), strike)
    # toks: [steps, batch] vs the clean twin sequence
    mismatch = toks != state["clean_toks"]
    return {
        "detected_steps": det_steps,
        "corrupted": jnp.any(mismatch),
        "divergence": jnp.mean(mismatch.astype(jnp.float32)),
        "loss_divergence": jnp.zeros((), jnp.float32),
    }


def _decode_clean(state, plan: CellPlan, key: jax.Array):
    del key
    params = jax.tree_util.tree_unflatten(state["treedef"],
                                          state["leaves"])
    _, _, metrics = state["decode"](params, state["cache"], state["tok"],
                                    state["pos"])
    errs = metrics.get("abft/qgemm_errors", 0) \
        + metrics.get("abft/embedding_bag_errors", 0) \
        + metrics.get("abft/kv_cache_errors", 0)
    return jnp.asarray(errs) > 0


def _decode_overhead(state, plan: CellPlan):
    if "decode_off" not in state:
        return None
    params, cache = state["params"], state["cache"]
    tok, pos = state["tok"], state["pos"]

    def protected():
        return state["decode"](params, cache, tok, pos)[0]

    def unprotected():
        return state["decode_off"](params, cache, tok, pos)[0]

    return protected, unprotected


register_target(InjectableTarget(
    name="decode_step",
    build=_decode_build, soak=_decode_soak, clean=_decode_clean,
    default_shapes=((2, 16),), shape_arity=2,
    overhead=_decode_overhead, victim_selectable=True))


__all__ = ["InjectableTarget", "TARGETS", "register_target", "get_target",
           "apply_fault", "DLRM_GEMM_SHAPES", "DECODE_ARCH"]

# training-step targets register themselves on import (kept in their own
# module — they pull in launch/optim/runtime machinery this module's
# operator targets never need)
from repro.campaign import targets_training  # noqa: E402,F401
