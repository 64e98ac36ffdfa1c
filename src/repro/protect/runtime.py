"""Protected-call runtime: resolve a plan rule, run the adapter, apply the
detect->act policy, emit the op-keyed report.

This is the single code path every protected call site goes through —
layers no longer hand-wire scheme/policy/threshold plumbing:

    c, rep = protected_call("qgemm", packed, x_q, ctx=ctx, name="attn.wq")

``ctx`` is duck-typed: anything with an optional ``plan``
(:class:`~repro.protect.plan.ProtectionPlan`) attribute plus the legacy
``abft`` / ``float_abft`` booleans the pre-plan ``Ctx`` carried.  With no
plan, the legacy flags reproduce the old behavior exactly (qgemm/EB gated
by ``abft``, float GEMMs by ``float_abft``, KV cache off).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core.policy import (abort_if_errors, empty_report, op_report,
                               with_recompute)
from repro.protect.ops import get_op
from repro.protect.plan import ProtectionPlan, ResolvedRule


def rule_for(ctx, op: str, name: str = "") -> ResolvedRule:
    """The plan rule governing op kind ``op`` at call site ``name``."""
    plan: Optional[ProtectionPlan] = getattr(ctx, "plan", None)
    if plan is not None:
        return plan.resolve(op, name)
    if ctx is None:
        return ResolvedRule()
    # legacy Ctx flags (pre-plan behavior, byte-for-byte)
    if op == "float_gemm":
        return ResolvedRule(enabled=bool(getattr(ctx, "float_abft", False)))
    if op in ("kv_cache", "kv_cache_paged"):
        return ResolvedRule(enabled=False)
    return ResolvedRule(enabled=bool(getattr(ctx, "abft", True)))


def protected_call(op: str, encoded, *inputs, ctx=None,
                   rule: Optional[ResolvedRule] = None, name: str = "",
                   **call_kwargs):
    """Run one protected op under its resolved plan rule.

    Returns ``(out, FaultReport)``.  Policy semantics:

    * ``log``       — verify, count, pass through;
    * ``recompute`` — ``lax.cond`` re-run up to ``rule.max_retries`` times
                      while errors persist (retries counted);
    * ``correct``   — adapters with ``supports_correct`` repair the single
                      flagged cell via row+column checksums; others fall
                      back to ``recompute`` (repair-or-retry);
    * ``abort``     — host callback raises
                      :class:`repro.core.policy.FaultAbort`.

    A disabled rule runs the adapter's unprotected baseline and reports
    zero checks.
    """
    adapter = get_op(op)
    if rule is None:
        rule = rule_for(ctx, op, name)
    if not rule.enabled:
        return adapter.unprotected(encoded, *inputs,
                                   **call_kwargs), empty_report()

    policy_name = rule.policy
    if policy_name == "correct" and not adapter.supports_correct:
        policy_name = "recompute"

    if policy_name == "correct":
        out, check = adapter(encoded, *inputs, rule=rule, **call_kwargs)
        out, residual, applied = adapter.correct(out, check)
        return out, op_report(op, residual, corrections=applied)

    if policy_name == "recompute":
        def run():
            o, c = adapter(encoded, *inputs, rule=rule, **call_kwargs)
            return o, c.err_count

        out, err, retries = with_recompute(
            run, max_retries=rule.max_retries)()
        return out, op_report(op, err, retries=retries)

    out, check = adapter(encoded, *inputs, rule=rule, **call_kwargs)
    if policy_name == "abort":
        jax.debug.callback(abort_if_errors, check.err_count)
    # adapters whose one call covers a variable amount of verified state
    # (e.g. pages touched by a paged KV read) report it via Check.aux so
    # the checks counter prices verification work, not call count
    # and an EmbeddingBag the slots it ran
    aux = check.aux if isinstance(check.aux, dict) else {}
    return out, op_report(op, check.err_count,
                          checks=aux.get("n_checks", 1),
                          eb_slots=aux.get("eb_slots"))


def observe_metrics(metrics, *, source: str, step: int = 0,
                    t_s: float = 0.0, obs=None, cell_id=None,
                    request_ids=(), bit_band=None, shard=None,
                    attrs=None) -> int:
    """Land one step's device-side FaultReport counters host-side.

    ``protected_call`` runs traced (jit/scan/vmap), so per-call host
    emission is impossible there — this is the single host-side choke
    point the consumers (serving engine, train loop, campaign executor)
    call with a step's ``device_get``'d metrics dict.  Increments the
    ``repro_abft_{checks,errors}_total`` counters (plus one
    ``repro_detections_total{op,source}`` inc per flagged op), emits one
    ``detection`` :class:`~repro.obs.FaultEvent` per flagged op kind,
    and — when anything was checked or the caller passed step ``attrs``
    (the serving engine's lane/tenant/duration context) — one
    ``info``/``channel=step`` summary event carrying the per-op
    (checks, errors) counts.  That summary is what feeds the live
    :class:`~repro.obs.Monitor` and makes ``repro.obs.replay`` exact.
    Returns the step's total residual errors; a ``None`` obs is a cheap
    no-op path that still returns the error count.
    """
    from repro.obs.events import op_counts

    counts = op_counts(metrics)
    errors = sum(errs for _, _, errs in counts)
    if obs is None:
        return errors
    from repro.obs import FaultEvent, events_from_metrics
    by_op = {}
    total_checks = 0
    for kind, checks, errs in counts:
        if checks or errs:
            by_op[kind] = [int(checks), int(errs)]
            total_checks += int(checks)
            obs.registry.counter(
                "repro_abft_checks_total",
                "ABFT checks by op kind").inc(checks, op=kind,
                                              source=source)
            obs.registry.counter(
                "repro_abft_errors_total",
                "residual ABFT errors by op kind").inc(errs, op=kind,
                                                       source=source)
        if errs > 0:
            labels = {"op": kind, "source": source}
            if cell_id:
                labels["cell"] = cell_id
            obs.registry.counter(
                "repro_detections_total",
                "detected faults by op kind, source, and cell"
            ).inc(1, **labels)
    obs.bus.extend(events_from_metrics(
        metrics, step=step, source=source, t_s=t_s, cell_id=cell_id,
        request_ids=tuple(request_ids), bit_band=bit_band, shard=shard))
    if by_op or attrs:
        obs.bus.emit(FaultEvent(
            op="step", step=step, source=source, kind="info", t_s=t_s,
            errors=int(errors), checks=total_checks, cell_id=cell_id,
            attrs={"channel": "step", "by_op": by_op, **(attrs or {})}))
    return errors


def kv_rule(ctx, name: str = "attn") -> ResolvedRule:
    """Convenience for attention layers: the kv_cache rule, additionally
    gated on the int8 serving path (``ctx.quant``) — a bf16 training cache
    has nothing to checksum."""
    r = rule_for(ctx, "kv_cache", name)
    if r.enabled and not bool(getattr(ctx, "quant", False)):
        return ResolvedRule(enabled=False, scheme=r.scheme, policy=r.policy,
                            rel_bound=r.rel_bound,
                            max_retries=r.max_retries,
                            threshold=r.threshold)
    return r


def paged_kv_rule(ctx, name: str = "attn") -> ResolvedRule:
    """The kv_cache_paged rule with its policy forced to ``log``.

    Page repair under recompute/abort is a host-side allocator action
    (evict the flagged page, rebuild prompt pages via re-prefill, or
    abort the owning request) — the serving engine applies it between
    steps.  In-jit the op can only count, so the traced call always
    logs; the plan's policy still decides what the engine does with the
    flag."""
    import dataclasses

    r = rule_for(ctx, "kv_cache_paged", name)
    return dataclasses.replace(r, policy="log")
