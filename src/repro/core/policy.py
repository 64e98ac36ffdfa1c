"""Fault reports and detect->act policies.

Every ABFT-protected op contributes to a :class:`FaultReport` — a small int32
pytree threaded functionally through layers, models, and step functions (it
scans/pmaps/pjits like any other pytree).  The report is **keyed by op kind**
(``qgemm``, ``float_gemm``, ``embedding_bag``, ``kv_cache``, plus anything
registered via :func:`register_op_kind`): per-kind check and error counters
ride in dicts, so a new protected operator extends the report by registering
a name instead of growing hard-coded fields.

Scan/vmap safety: pytree structure must be static under tracing, so every
constructor (:func:`empty_report`, :func:`op_report`) materializes counters
for ALL registered kinds — a scan carry built from ``empty_report()`` always
matches the body's merged reports.  Register custom kinds at import time,
before tracing.

Policies decide what a step does when ``report.total_errors() > 0``:

- ``log``       — surface counts in step metrics (default; zero control flow)
- ``recompute`` — re-run the op under ``lax.cond`` (paper §I: an error that
                  strikes twice is vanishingly rare, so one deterministic
                  retry clears transient faults; retries are counted)
- ``correct``   — repair the single flagged cell in place via the row +
                  column checksums (abft_gemm.correct_single_error); multi
                  error results fall through with their error count intact
- ``abort``     — raise via a host callback (used by serving: fail the
                  request, not the server)

``POLICIES`` maps the names to wrappers; ``apply_policy(name, op)`` is the
string-driven entry point.  The declarative front door over all of this is
:mod:`repro.protect` — per-op-pattern :class:`~repro.protect.ProtectionPlan`
rules resolve to one of these policies per protected call site.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import jax
import jax.numpy as jnp

#: built-in op kinds — one per registered protected-op adapter
#: (repro.protect.ops registers its adapters against these names).
_DEFAULT_OP_KINDS = ("qgemm", "float_gemm", "embedding_bag", "kv_cache")
_OP_KINDS = list(_DEFAULT_OP_KINDS)


def op_kinds() -> tuple:
    """Currently registered op kinds (report key set)."""
    return tuple(_OP_KINDS)


def register_op_kind(name: str) -> None:
    """Add an op kind to the report key set.  Call at import time (before
    any tracing) so report pytree structure stays static."""
    if name not in _OP_KINDS:
        _OP_KINDS.append(name)


def _zero() -> jax.Array:
    return jnp.zeros((), jnp.int32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FaultReport:
    """Per-op-kind ABFT counters.

    ``checks[name]`` / ``errors[name]`` count verified calls and residual
    (post-policy) errors per op kind; ``retries`` and ``corrections``
    aggregate the recompute/correct policy actions across all kinds.
    ``eb_slots`` counts the EmbeddingBag slots (bag positions, −1 pads
    included) the step's checked lookups ran, as the kernel's grid holds
    them: work, fixed by the step's shapes.
    """
    checks: Dict[str, jax.Array]
    errors: Dict[str, jax.Array]
    retries: jax.Array
    corrections: jax.Array
    eb_slots: jax.Array = 0

    def tree_flatten(self):
        names = tuple(sorted(self.checks))
        children = (tuple(self.checks[n] for n in names)
                    + tuple(self.errors[n] for n in names)
                    + (self.retries, self.corrections, self.eb_slots))
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        k = len(names)
        return cls(dict(zip(names, children[:k])),
                   dict(zip(names, children[k:2 * k])),
                   *children[2 * k:])

    # ------------------------------ queries ---------------------------------

    def _get(self, table: Dict[str, jax.Array], name: str):
        return table.get(name, _zero())

    def total_errors(self) -> jax.Array:
        return sum(self.errors.values(), _zero())

    def total_checks(self) -> jax.Array:
        return sum(self.checks.values(), _zero())

    def as_metrics(self) -> dict:
        m = {}
        for n in sorted(self.checks):
            m[f"abft/{n}_checks"] = self.checks[n]
            m[f"abft/{n}_errors"] = self.errors[n]
        m["abft/retries"] = self.retries
        m["abft/corrections"] = self.corrections
        m["abft/eb_slots"] = self.eb_slots
        # legacy aliases (pre-protect metric names; gemm = int8 + float)
        m["abft/gemm_checks"] = self.gemm_checks
        m["abft/gemm_errors"] = self.gemm_errors
        m["abft/eb_checks"] = self.eb_checks
        m["abft/eb_errors"] = self.eb_errors
        m["abft/recomputes"] = self.retries
        return m

    # legacy field names, kept as views over the keyed counters ---------------

    @property
    def gemm_checks(self):
        return self._get(self.checks, "qgemm") + self._get(self.checks,
                                                           "float_gemm")

    @property
    def gemm_errors(self):
        return self._get(self.errors, "qgemm") + self._get(self.errors,
                                                           "float_gemm")

    @property
    def eb_checks(self):
        return self._get(self.checks, "embedding_bag")

    @property
    def eb_errors(self):
        return self._get(self.errors, "embedding_bag")

    @property
    def recomputes(self):
        return self.retries


def empty_report() -> FaultReport:
    z = _zero()
    return FaultReport({n: z for n in _OP_KINDS},
                       {n: z for n in _OP_KINDS}, z, z, z)


def op_report(name: str, err_count, *, checks=1, retries=None,
              corrections=None, eb_slots=None) -> FaultReport:
    """A report with one op kind's counters set (all other kinds zero)."""
    if name not in _OP_KINDS:
        raise KeyError(f"unregistered op kind {name!r}; have {_OP_KINDS} "
                       "(register_op_kind at import time)")
    rep = empty_report()
    rep.checks[name] = jnp.asarray(checks, jnp.int32)
    rep.errors[name] = jnp.asarray(err_count, jnp.int32)
    if retries is not None:
        rep.retries = jnp.asarray(retries, jnp.int32)
    if corrections is not None:
        rep.corrections = jnp.asarray(corrections, jnp.int32)
    if eb_slots is not None:
        rep.eb_slots = jnp.asarray(eb_slots, jnp.int32)
    return rep


def gemm_report(err_count: jax.Array, recomputes=None) -> FaultReport:
    """Legacy helper: one verified int8 GEMM."""
    return op_report("qgemm", err_count, retries=recomputes)


def eb_report(err_count: jax.Array) -> FaultReport:
    """Legacy helper: one verified EmbeddingBag."""
    return op_report("embedding_bag", err_count)


def merge_reports(*reports: FaultReport) -> FaultReport:
    if not reports:
        return empty_report()
    names = sorted(set().union(*(r.checks.keys() for r in reports)))
    z = _zero()
    return FaultReport(
        {n: sum((r._get(r.checks, n) for r in reports), z) for n in names},
        {n: sum((r._get(r.errors, n) for r in reports), z) for n in names},
        sum((r.retries for r in reports), z),
        sum((r.corrections for r in reports), z),
        sum((r.eb_slots for r in reports), z))


def with_recompute(op: Callable, max_retries: int = 1):
    """Wrap an ABFT op ``op() -> (out, err_count)`` with detect->recompute.

    In simulation a deterministic re-run returns the same value; on real
    hardware a transient fault does not recur.  What matters structurally is
    the control flow (lax.cond) and the retry accounting, both preserved.
    """
    def wrapped(*args, **kwargs):
        out, err = op(*args, **kwargs)
        retries = jnp.zeros((), jnp.int32)
        for _ in range(max_retries):
            def retry(_):
                o2, e2 = op(*args, **kwargs)
                return o2, e2, jnp.ones((), jnp.int32)

            def keep(_):
                return out, err, jnp.zeros((), jnp.int32)

            out, err, did = jax.lax.cond(err > 0, retry, keep, None)
            retries = retries + did
        return out, err, retries

    return wrapped


def with_log(op: Callable):
    """Policy ``log``: pass-through with zero retries (uniform arity with
    the other policies: ``op() -> (out, err)`` becomes
    ``(out, err, retries)``)."""
    def wrapped(*args, **kwargs):
        out, err = op(*args, **kwargs)
        return out, err, jnp.zeros((), jnp.int32)

    return wrapped


def with_correct(op: Callable):
    """Policy ``correct``: single-error repair via row+column checksums.

    ``op() -> (c, err_rows, err_count, col_check)`` where ``col_check`` is
    the exact expected int32 column-sum vector
    (:func:`repro.core.abft_gemm.encode_activation_checksum` of A, times
    B).  A successfully repaired result reports zero residual errors; a
    multi-error result keeps its count so an outer recompute/abort layer
    still sees it.  Returns ``(c, err_count, corrections)``.
    """
    from repro.core.abft_gemm import correct_single_error

    def wrapped(*args, **kwargs):
        c, err_rows, err_count, col_check = op(*args, **kwargs)
        corrected, applied = correct_single_error(c, err_rows, col_check)
        residual = jnp.where(applied, 0, err_count).astype(jnp.int32)
        return corrected, residual, applied.astype(jnp.int32)

    return wrapped


class FaultAbort(RuntimeError):
    """Raised host-side by policy ``abort`` when an op reports errors."""


def is_fault_abort(exc: BaseException) -> bool:
    """True for a :class:`FaultAbort` OR the runtime error jit wraps it in.

    Inside jit, jax surfaces callback exceptions as ``XlaRuntimeError``
    (the FaultAbort text is preserved in the message); request boundaries
    should gate on this predicate rather than ``except FaultAbort``.
    """
    return isinstance(exc, FaultAbort) or "FaultAbort" in repr(exc)


def abort_if_errors(err) -> None:
    """Host callback body for policy ``abort`` (shared with repro.protect)."""
    if int(err) > 0:
        raise FaultAbort(f"ABFT detected {int(err)} corrupted op(s)")


def with_abort(op: Callable):
    """Policy ``abort``: host-level raise when ``err > 0`` (serving: fail
    the REQUEST, never the server).  Eager callers catch
    :class:`FaultAbort`; jitted callers get it re-wrapped by the runtime,
    so request boundaries use :func:`is_fault_abort` on the caught
    exception."""
    def wrapped(*args, **kwargs):
        out, err = op(*args, **kwargs)
        jax.debug.callback(abort_if_errors, err)
        return out, err, jnp.zeros((), jnp.int32)

    return wrapped


#: name -> wrapper; ``correct`` expects the 4-tuple GEMM contract (see
#: :func:`with_correct`), the rest wrap any ``op() -> (out, err)``.
POLICIES = {
    "log": with_log,
    "recompute": with_recompute,
    "correct": with_correct,
    "abort": with_abort,
}


def apply_policy(name: str, op: Callable, **kwargs):
    if name not in POLICIES:
        raise KeyError(f"unknown policy {name!r}; have {sorted(POLICIES)}")
    return POLICIES[name](op, **kwargs)
