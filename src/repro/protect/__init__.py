"""Unified declarative protection over every ABFT operator.

One API answers "which ops are protected, how, and with what
policy/threshold" for the whole stack:

* :class:`ProtectionPlan` / :class:`OpRule` — ordered per-op-pattern rules
  (``"qgemm/attn.*:policy=recompute,embedding_bag:off"``), parseable from
  CLI strings and config dicts;
* :class:`~repro.protect.ops.ProtectedOp` adapters — uniform
  ``encode / __call__ / unprotected`` over int8 GEMM and EmbeddingBag
  (Pallas or the XLA reference via :mod:`repro.kernels.ops`), float GEMM,
  and the quantized KV cache;
* :func:`protected_call` — the single runtime every layer call site goes
  through (rule resolution, scheme dispatch, per-op policy application:
  log / recompute / correct / abort);
* :class:`~repro.core.policy.FaultReport` — op-name-keyed counters threaded
  as a pytree through jit/scan/vmap;
* :func:`protect` — wrap a model apply function so serving and experiments
  select protection purely by plan.

    from repro.protect import ProtectionPlan, protect
    plan = ProtectionPlan.parse("*:policy=log,kv_cache:on")
    prefill = protect(model.prefill, plan)
    (logits, cache), report = prefill(params, batch, cache_len=256)
"""
from repro.core.policy import (FaultReport, empty_report, merge_reports,
                               op_kinds, op_report, register_op_kind)
from repro.protect.api import Protected, encode_tree, protect
from repro.protect.ops import (Check, OPS, ProtectedOp, get_op,
                               register_op)
from repro.protect.plan import (OpRule, POLICY_NAMES, ProtectionPlan,
                                ResolvedRule, default_plan,
                                unprotected_plan)
from repro.protect.runtime import (kv_rule, observe_metrics,
                                   protected_call, rule_for)

__all__ = [
    "ProtectionPlan", "OpRule", "ResolvedRule", "POLICY_NAMES",
    "default_plan", "unprotected_plan",
    "ProtectedOp", "Check", "OPS", "register_op", "get_op",
    "protected_call", "rule_for", "kv_rule", "observe_metrics",
    "protect", "Protected", "encode_tree",
    "FaultReport", "op_report", "empty_report", "merge_reports",
    "op_kinds", "register_op_kind",
]
