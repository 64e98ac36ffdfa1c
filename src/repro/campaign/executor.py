"""Batched campaign executor.

One cell = thousands of (inject → run → count) trials.  The ad-hoc
benchmark scripts this subsystem replaces ran Python loops per scenario;
here every cell is ONE jitted ``vmap`` over a key batch (chunked to bound
memory), and with multiple host devices the chunks are ``pmap``'d so a
`--device-count 8` sweep runs eight chunks abreast.

Multi-device is also a CELL axis, not just a trial axis: a plan with
``data_shards`` > 1 gets its own slice of the host mesh
(:func:`repro.sharding.make_data_mesh` over devices forced with
``XLA_FLAGS=--xla_force_host_platform_device_count``) and its soak runs
under shard_map so ``checked_psum`` verifies a real collective.  Cells
are placed round-robin over the disjoint mesh slices — the sweep itself
is sharded, which is what a fleet-scale runner needs for locality.

The executor is target-agnostic: it only sees the three pure functions a
target registers (build / trial / clean) plus optional overhead thunks it
times with a median-of-iters wall clock.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.campaign.metrics import (CellMetrics, compute_metrics,
                                    merge_shard_detections)
from repro.campaign.spec import CampaignSpec, CellPlan, expand
from repro.campaign.targets import get_target

#: default trials per compiled vmap chunk — bounds per-chunk memory for
#: targets that materialize a corrupted copy of their state per trial.
CHUNK = 256


@dataclasses.dataclass
class CellResult:
    plan: CellPlan
    metrics: CellMetrics
    seconds: float


def resolve_device_count(requested: Optional[int] = None, *,
                         strict: bool = False) -> int:
    """Validate a requested host-device count against what jax actually
    has.  ``--device-count`` only works when XLA_FLAGS lands before jax
    initializes; when it didn't (library use, jax already imported), the
    old behavior was to trust the caller and die in a pmap reshape —
    now we warn and fall back to ``jax.local_device_count()``.

    ``strict=True`` (a run that needs real chips, not a forced host
    platform) makes too few devices an error instead."""
    avail = jax.local_device_count()
    if strict and requested and requested > avail:
        raise RuntimeError(f"requested {requested} devices but only "
                           f"{avail} exist")
    if requested and requested > avail:
        warnings.warn(
            f"requested {requested} host devices but only {avail} exist "
            f"(XLA_FLAGS must be set before jax initializes); falling "
            f"back to {avail}", UserWarning, stacklevel=2)
        return avail
    return requested or avail


def _cell_mesh(plan: CellPlan, slot: int = 0):
    """-> (mesh | None, effective_shards) for one cell.

    Sharded cells land on slices of the host platform assigned
    round-robin by ``slot`` (the cell's index among sharded cells): with
    8 devices and 2-shard cells, four cells run on four disjoint slices
    — the sweep is sharded, not just each cell's trials.  (Disjointness
    holds per shard width; a sweep mixing widths can overlap slices,
    harmless while cells execute sequentially — a concurrent fleet
    runner would need a real slice allocator.)  A host with fewer
    devices than ``plan.data_shards`` degrades to what exists (with a
    warning) instead of failing inside Mesh construction."""
    if plan.data_shards <= 1:
        return None, 1
    shards = min(plan.data_shards, jax.local_device_count())
    if shards < plan.data_shards:
        warnings.warn(
            f"cell {plan.cell_id}: data_shards={plan.data_shards} > "
            f"{shards} available host devices; running at {shards} "
            f"shard(s) (collective_verified will record the degradation)",
            UserWarning, stacklevel=2)
    if shards == 1:
        return None, 1
    devs = jax.local_devices()
    n_slices = len(devs) // shards
    start = (slot % n_slices) * shards
    from repro.sharding import make_data_mesh
    return make_data_mesh(shards, devices=devs[start:start + shards]), \
        shards


def _chunked_counts(fn: Callable, keys: jax.Array, chunk: int,
                    n_outputs: int) -> np.ndarray:
    """Run ``fn(key) -> bool tuple`` over all keys; returns summed counts
    [n_outputs] (plus, for 2-output trial fns, the AND of both flags as a
    third count).  Chunks share at most two jit caches (full chunk +
    remainder); multi-device hosts split each chunk across devices with
    pmap(vmap(...)).
    """
    devs = jax.local_devices()
    ndev = len(devs)

    def batch(ks):
        outs = jax.vmap(fn)(ks)
        outs = outs if isinstance(outs, tuple) else (outs,)
        counts = [jnp.sum(o.astype(jnp.int32)) for o in outs]
        if len(outs) == 2:
            counts.append(jnp.sum((outs[0] & outs[1]).astype(jnp.int32)))
        return jnp.stack(counts)

    jbatch = jax.jit(batch)
    pbatch = jax.pmap(batch) if ndev > 1 else None

    total = np.zeros(n_outputs + (1 if n_outputs == 2 else 0), np.int64)
    i, n = 0, keys.shape[0]
    while i < n:
        take = min(chunk * max(ndev, 1), n - i)
        ks = keys[i:i + take]
        if pbatch is not None and take % ndev == 0 and take >= ndev:
            counts = pbatch(ks.reshape((ndev, take // ndev)
                                       + ks.shape[1:])).sum(axis=0)
        else:
            counts = jbatch(ks)
        total += np.asarray(counts, np.int64)
        i += take
    return total


def _chunked_soak(fn: Callable, keys: jax.Array, chunk: int,
                  steps: int) -> dict:
    """Run a soak-protocol target over all keys.

    ``fn(key) -> {"detected_steps": bool [steps], "corrupted": bool,
    "divergence": f32, "loss_divergence": f32}``.  Returns host-side
    aggregates: detection/corruption/escape counts, the per-step
    first-detection latency histogram, and divergence stats.  Multi-device
    hosts split each chunk across devices with pmap(vmap(...)), like
    :func:`_chunked_counts`.
    """
    def batch(ks):
        out = jax.vmap(fn)(ks)
        det_steps = out["detected_steps"].astype(jnp.int32)   # [B, steps]
        detected = jnp.any(det_steps > 0, axis=1)
        corrupted = out["corrupted"]
        first = jnp.argmax(det_steps, axis=1)                 # [B]
        # histogram of first-detection latency, detected trials only
        hist = jnp.sum(
            (first[:, None] == jnp.arange(steps)[None, :])
            & detected[:, None], axis=0).astype(jnp.int32)
        return {
            "detected": jnp.sum(detected.astype(jnp.int32)),
            "corrupted": jnp.sum(corrupted.astype(jnp.int32)),
            "det_and_cor": jnp.sum((detected & corrupted)
                                   .astype(jnp.int32)),
            "hist": hist,
            "div_sum": jnp.sum(out["divergence"]),
            "div_max": jnp.max(out["divergence"]),
            "loss_div_sum": jnp.sum(out["loss_divergence"]),
        }

    ndev = len(jax.local_devices())
    jbatch = jax.jit(batch)
    pbatch = jax.pmap(batch) if ndev > 1 else None

    total = {"detected": 0, "corrupted": 0, "det_and_cor": 0,
             "hist": np.zeros(steps, np.int64), "div_sum": 0.0,
             "div_max": 0.0, "loss_div_sum": 0.0}
    i, n = 0, keys.shape[0]
    while i < n:
        take = min(chunk * max(ndev, 1), n - i)
        ks = keys[i:i + take]
        if pbatch is not None and take % ndev == 0 and take >= ndev:
            out = jax.device_get(pbatch(
                ks.reshape((ndev, take // ndev) + ks.shape[1:])))
            out = {k: (v.max(axis=0) if k == "div_max" else v.sum(axis=0))
                   for k, v in out.items()}
        else:
            out = jax.device_get(jbatch(ks))
        for k in ("detected", "corrupted", "det_and_cor"):
            total[k] += int(out[k])
        total["hist"] += np.asarray(out["hist"], np.int64)
        total["div_sum"] += float(out["div_sum"])
        total["div_max"] = max(total["div_max"], float(out["div_max"]))
        total["loss_div_sum"] += float(out["loss_div_sum"])
        i += take
    return total


def _sharded_soak(fn: Callable, keys: jax.Array, steps: int,
                  shards: int) -> dict:
    """Run a soak-protocol target whose trial executes under a shard_map
    mesh.  Trials run one jitted call at a time (a sharded trial already
    occupies its whole mesh slice; vmapping over shard_map would fuse
    trial and mesh batching) and the per-shard ``shard_detected`` flags
    are folded with :func:`merge_shard_detections` — same aggregates as
    :func:`_chunked_soak` plus the per-shard column.
    """
    jfn = jax.jit(fn)
    total = {"detected": 0, "corrupted": 0, "det_and_cor": 0,
             "hist": np.zeros(steps, np.int64), "div_sum": 0.0,
             "div_max": 0.0, "loss_div_sum": 0.0}
    per_trial_shards: List[np.ndarray] = []
    for i in range(keys.shape[0]):
        out = jax.device_get(jfn(keys[i]))
        det_steps = np.asarray(out["detected_steps"], bool)
        detected = bool(det_steps.any())
        corrupted = bool(out["corrupted"])
        total["detected"] += detected
        total["corrupted"] += corrupted
        total["det_and_cor"] += detected and corrupted
        if detected:
            total["hist"][int(np.argmax(det_steps))] += 1
        total["div_sum"] += float(out["divergence"])
        total["div_max"] = max(total["div_max"],
                               float(out["divergence"]))
        total["loss_div_sum"] += float(out["loss_divergence"])
        per_trial_shards.append(
            np.asarray(out["shard_detected"], np.int64))
    total["shard_detections"] = merge_shard_detections(per_trial_shards) \
        or [0] * shards
    return total


def _median_time(fn: Callable) -> float:
    from repro.campaign.timing import median_time
    return median_time(jax.jit(fn))


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _cell_span(obs, name: str, plan: CellPlan):
    """A tracer span for one cell phase, or a no-op without obs."""
    if obs is None:
        return _NullSpan()
    return obs.tracer.span(name, cat="campaign", cell=plan.cell_id)


def _publish_cell(obs, plan: CellPlan, metrics: CellMetrics) -> None:
    """Land one finished cell in the obs layer: outcome counters labeled
    by cell id (the Prometheus face of the artifact's CellMetrics) and a
    summary ``cell`` event carrying detector value vs analytic bound."""
    if obs is None:
        return
    from repro.obs import FaultEvent
    reg = obs.registry
    labels = {"cell": plan.cell_id}
    reg.counter("repro_injections_total",
                "injected faults per cell").inc(metrics.samples, **labels)
    reg.counter("repro_detections_total",
                "detected (or masked) faults per cell").inc(
                    metrics.effective_detected, **labels)
    reg.counter("repro_escapes_total",
                "undetected corruptions (SDC) per cell").inc(
                    metrics.escapes, **labels)
    reg.counter("repro_false_positives_total",
                "clean-run flags per cell").inc(
                    metrics.false_positives, **labels)
    obs.bus.emit(FaultEvent(
        op=plan.target, step=0, source="campaign.executor", kind="cell",
        t_s=obs.tracer.now_s(), errors=metrics.detected,
        checks=metrics.samples, cell_id=plan.cell_id,
        bit_band=plan.bit_band,
        detector_value=metrics.detection_rate,
        bound=metrics.analytic_bound,
        attrs={"escapes": metrics.escapes,
               "false_positives": metrics.false_positives,
               "fp_rate": metrics.fp_rate,
               # what the {cell} detections counter was inc'd with —
               # replay reads it so round-trip stays counter-exact
               # (detected alone misses masked-by-recompute trials)
               "effective_detected": metrics.effective_detected,
               "clean_samples": metrics.clean_samples}))
    if metrics.false_positives:
        obs.bus.emit(FaultEvent(
            op=plan.target, step=0, source="campaign.executor",
            kind="false_positive", t_s=obs.tracer.now_s(),
            errors=metrics.false_positives,
            checks=metrics.clean_samples, cell_id=plan.cell_id,
            bit_band=plan.bit_band))


def run_cell(plan: CellPlan, *, chunk: int = CHUNK,
             slot: int = 0, obs=None, monitor=None) -> CellResult:
    if monitor is not None and obs is not None:
        monitor.bind(obs)          # cell events tick the health machine
    target = get_target(plan.target)
    t0 = time.perf_counter()
    key = jax.random.key(plan.seed)
    k_build, k_trial, k_clean = jax.random.split(key, 3)

    mesh, eff_shards = (_cell_mesh(plan, slot) if target.shardable
                        else (None, 1))
    with _cell_span(obs, "build", plan):
        if target.shardable:
            state = target.build(plan, k_build, mesh=mesh)
        else:
            state = target.build(plan, k_build)

    soak_extras: dict = {}
    if target.soak is not None:
        trial_keys = jax.random.split(k_trial, plan.samples)
        with _cell_span(obs, "trials", plan):
            if mesh is not None:
                agg = _sharded_soak(
                    lambda k: target.soak(state, plan, k),
                    trial_keys, plan.steps, eff_shards)
            else:
                agg = _chunked_soak(
                    lambda k: target.soak(state, plan, k),
                    trial_keys, chunk, plan.steps)
        detected = agg["detected"]
        corrupted = agg["corrupted"]
        det_and_cor = agg["det_and_cor"]
        soak_extras = {
            "steps": plan.steps,
            "detection_latency_hist": [int(c) for c in agg["hist"]],
            "divergence_mean": agg["div_sum"] / plan.samples,
            "divergence_max": agg["div_max"],
            "loss_divergence_mean": agg["loss_div_sum"] / plan.samples,
            "shards": eff_shards,
            # True only when the PLANNED multi-device collective ran: a
            # cell degraded to fewer shards (or to the single-device
            # fallback) must not read as mesh-verified even though a
            # smaller real collective may have executed — `shards` says
            # what actually ran
            "collective_verified": (eff_shards > 1
                                    and eff_shards == plan.data_shards),
            "shard_detections": agg.get("shard_detections"),
        }
    else:
        with _cell_span(obs, "trials", plan):
            trial_counts = _chunked_counts(
                lambda k: target.trial(state, plan, k),
                jax.random.split(k_trial, plan.samples), chunk, 2)
        detected, corrupted, det_and_cor = (int(c) for c in trial_counts)

    false_positives = 0
    if plan.clean_samples > 0:
        with _cell_span(obs, "clean", plan):
            clean_counts = _chunked_counts(
                lambda k: target.clean(state, plan, k),
                jax.random.split(k_clean, plan.clean_samples), chunk, 1)
        false_positives = int(clean_counts[0])

    protected_s = unprotected_s = None
    overhead_breakdown = None
    if plan.measure_overhead and target.overhead is not None:
        pair = target.overhead(state, plan)
        if pair is not None:
            prot, unprot = pair
            with _cell_span(obs, "overhead", plan):
                protected_s = _median_time(prot)
                unprotected_s = _median_time(unprot)
    if plan.measure_overhead and target.overhead_phases is not None:
        from repro.campaign.timing import phase_breakdown
        phases = target.overhead_phases(state, plan)
        if phases:
            overhead_breakdown = phase_breakdown(
                phases, tracer=obs.tracer if obs is not None else None,
                cell=plan.cell_id)

    metrics = compute_metrics(
        samples=plan.samples, detected=detected, corrupted=corrupted,
        detected_and_corrupted=det_and_cor,
        clean_samples=plan.clean_samples,
        false_positives=false_positives,
        analytic_bound=target.analytic_bound(plan),
        protected_s=protected_s, unprotected_s=unprotected_s,
        overhead_breakdown=overhead_breakdown,
        **soak_extras)
    _publish_cell(obs, plan, metrics)
    return CellResult(plan=plan, metrics=metrics,
                      seconds=time.perf_counter() - t0)


def run_specs(specs: Sequence[CampaignSpec], *, chunk: int = CHUNK,
              verbose: Optional[Callable[[str], None]] = None,
              obs=None, monitor=None
              ) -> Tuple[List[CellResult], List[dict]]:
    """Expand and execute a list of specs; returns (results, skipped)."""
    results: List[CellResult] = []
    skipped: List[dict] = []
    n_sharded = 0
    for spec in specs:
        plans, skips = expand(spec)
        skipped.extend(skips)
        for plan in plans:
            # sharded cells take successive mesh slices (round-robin)
            slot = n_sharded
            if plan.data_shards > 1:
                n_sharded += 1
            r = run_cell(plan, chunk=chunk, slot=slot, obs=obs,
                         monitor=monitor)
            results.append(r)
            if verbose:
                m = r.metrics
                verbose(f"[{r.plan.cell_id}] n={m.samples} "
                        f"detect={m.detection_rate:.4f} "
                        f"escape={m.escape_rate:.4f} fp={m.fp_rate:.4f} "
                        f"({r.seconds:.1f}s)")
    return results, skipped


def run_campaign(name: str, specs: Sequence[CampaignSpec], *,
                 out_dir: Optional[str] = None, chunk: int = CHUNK,
                 verbose: Optional[Callable[[str], None]] = None,
                 obs=None, monitor=None) -> dict:
    """Execute specs, assemble the artifact dict, optionally write it.
    ``obs`` (a :class:`repro.obs.Observability`) records per-phase spans,
    cell summary events, and outcome counters alongside the artifact;
    ``monitor`` (a :class:`repro.obs.Monitor`) additionally watches the
    published cell outcomes and drives per-cell health states."""
    from repro.campaign.artifacts import campaign_to_dict, write_artifacts

    t0 = time.perf_counter()
    results, skipped = run_specs(specs, chunk=chunk, verbose=verbose,
                                 obs=obs, monitor=monitor)
    result = campaign_to_dict(
        name, list(specs),
        [{"plan": r.plan, "metrics": r.metrics, "seconds": r.seconds}
         for r in results],
        skipped, wall_s=time.perf_counter() - t0,
        seed=specs[0].seed if specs else 0)
    if out_dir is not None:
        write_artifacts(result, out_dir)
    return result
