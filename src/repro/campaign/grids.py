"""Named campaign grids.

``quick``  — the CI smoke: every target class exercised, minutes on CPU,
             sample counts sized so the GEMM bit-flip cell is statistically
             comparable (±2%) to the §IV-C analytic bound.
``paper``  — the paper's Tables II + III campaigns at full shape coverage.
``thresholds`` — EB rel_bound sweep: detection-vs-FP tradeoff per bit band.
``pallas`` — fused-kernel parity: the identical bit-flip grid through the
             fused Pallas path (interpret mode on CPU) and the XLA paths,
             gating on overlapping detection CIs + the overhead columns.
``soak``   — the full-model decode-step sweep across fault models/bands.
``victims`` — decode-soak victim sweep: which leaf gets flipped, addressed
             by protect-plan path patterns (``attn.wq``, ``mlp.down``, ...).
``training`` — training-step resilience: faults at every seam of the
             compressed-gradient optimizer pipeline (pre/post checked_psum,
             int8 payload, error feedback, AdamW moments) plus multi-step
             persistent-fault soaks with detection-latency histograms.
``multidevice`` — mesh-sharded training soaks: cells run under shard_map
             over a fake ``data`` axis so checked_psum verifies a REAL
             collective per step (single-shard transit flips, the
             post-reduction window, and a sharded-vs-single contrast).
``full``   — everything above plus the beyond-paper KV-cache cells.

(The ``serving_soak`` grid — faults under live traffic — lives in
:mod:`repro.serving.soak`; the CLI dispatches to it.)
"""
from __future__ import annotations

from typing import Dict, List

from repro.campaign.spec import CampaignSpec, DLRM_GEMM_SHAPES


def quick_specs(seed: int = 0, samples: int = 600) -> List[CampaignSpec]:
    return [
        CampaignSpec(
            name="quick-gemm",
            targets=("gemm_packed", "gemm_c"),
            fault_models=("bitflip", "random_value"),
            bit_bands=("all",),
            shapes=((1, 256, 512), (20, 256, 512)),
            dtypes=("int8", "int32"),
            samples=max(samples, 500), seed=seed,
            measure_overhead=True),
        CampaignSpec(
            name="quick-eb",
            targets=("embedding_bag",),
            fault_models=("bitflip",),
            bit_bands=("significant", "low"),
            samples=500, seed=seed, measure_overhead=True),
        CampaignSpec(
            name="quick-kv",
            targets=("kv_cache",),
            fault_models=("bitflip",),
            bit_bands=("all",),
            dtypes=("int8", "float32"),
            samples=200, seed=seed),
        CampaignSpec(
            name="quick-soak",
            targets=("decode_step",),
            fault_models=("bitflip",),
            bit_bands=("significant",),
            samples=8, clean_samples=4, seed=seed),
    ]


def paper_specs(seed: int = 0, quick: bool = False) -> List[CampaignSpec]:
    """Tables II (GEMM, 28 DLRM shapes × B/C errors × clean) and III
    (EmbeddingBag high/low bands + clean)."""
    shapes = tuple(DLRM_GEMM_SHAPES[::4] if quick else DLRM_GEMM_SHAPES)
    return [
        CampaignSpec(
            name="paper-gemm",
            targets=("gemm_packed", "gemm_c"),
            fault_models=("bitflip",),
            bit_bands=("all",),
            shapes=shapes,
            dtypes=("int8", "int32"),
            samples=100, seed=seed),
        CampaignSpec(
            name="paper-eb",
            targets=("embedding_bag",),
            fault_models=("bitflip",),
            bit_bands=("significant", "low"),
            samples=200, clean_samples=400, seed=seed),
    ]


def thresholds_specs(seed: int = 0,
                     samples: int = 400) -> List[CampaignSpec]:
    """EB ``rel_bound`` sweep: the detection-vs-false-positive tradeoff
    curve per bit band (ROADMAP open item).  Tight bounds catch low-bit
    flips but false-positive on round-off; the paper's 1e-5 sits between.
    Clean samples run at every bound so the FP side of the curve is
    measured, not assumed."""
    return [CampaignSpec(
        name="eb-thresholds",
        targets=("embedding_bag",),
        fault_models=("bitflip",),
        bit_bands=("significant", "low", "sign"),
        rel_bounds=(1e-7, 1e-6, 1e-5, 1e-4, 1e-3),
        samples=samples, clean_samples=samples, seed=seed)]


def pallas_specs(seed: int = 0, quick: bool = False,
                 samples: int = 0) -> List[CampaignSpec]:
    """Fused-kernel detection parity: run the SAME bit-flip grid through
    the fused Pallas implementation and the XLA reference, so the
    artifact holds fused vs packed detection rates side by side.  Cell
    seeds derive from cell ids (which include the target name), so the
    fused and reference cells draw
    *different* fault samples — the parity gate is therefore statistical:
    overlapping 95% Wilson intervals on the same grid point
    (:func:`repro.campaign.diff` compares detection the same way).  A
    deterministic bit-exact parity check (same flips through both paths)
    lives in tests/test_kernels.py; this grid measures at campaign scale
    and times the fused kernel (interpret mode on CPU — wall-clock for
    parity, not a TPU latency claim; chip numbers come from the chip
    benchmark).

    The EB cells run BOTH targets at the pallas-sized shape so cells stay
    comparable (interpret-mode emulation makes the default EB shape
    needlessly slow)."""
    n = samples or (400 if quick else 800)
    gemm = CampaignSpec(
        name="pallas-gemm",
        targets=("gemm_pallas", "gemm_packed"),
        fault_models=("bitflip",),
        bit_bands=("all",),
        shapes=((20, 256, 512),),
        samples=n, clean_samples=max(64, n // 4), seed=seed,
        measure_overhead=True)
    eb = CampaignSpec(
        name="pallas-eb",
        targets=("eb_pallas", "embedding_bag"),
        fault_models=("bitflip",),
        bit_bands=("significant", "low"),
        shapes=((2000, 64, 8, 32),),
        samples=n, clean_samples=max(64, n // 4), seed=seed,
        measure_overhead=True)
    return [gemm, eb]


#: the decode soak's victim sweep: one packed projection per layer role,
#: plus the token table — the per-layer "which leaf gets flipped" axis the
#: protect plan's path vocabulary makes addressable (ROADMAP item).
VICTIM_PATTERNS = ("attn.wq", "attn.wk", "attn.wo", "mlp.up", "mlp.down",
                   "embed.table", "lm_head")


def victims_specs(seed: int = 0, samples: int = 12) -> List[CampaignSpec]:
    """Per-layer victim selection in the decode soak: sweep which leaf of
    the reduced LM gets flipped (path patterns in the protect-plan
    vocabulary) and compare end-to-end detection/escape per victim —
    attention projections vs MLP vs the embedding table behave very
    differently (an untouched-row table flip is invisible by
    construction)."""
    return [CampaignSpec(
        name="decode-victims",
        targets=("decode_step",),
        fault_models=("bitflip",),
        bit_bands=("significant",),
        victims=VICTIM_PATTERNS,
        samples=samples, clean_samples=4, seed=seed)]


#: every training-pipeline injection seam (repro.campaign.targets_training)
TRAINING_TARGETS = ("train_grad_pre", "train_payload", "train_grad_post",
                    "train_moments")


def training_specs(seed: int = 0, quick: bool = False,
                   samples: int = 0) -> List[CampaignSpec]:
    """Training-step resilience (ROADMAP item): real optimizer steps with
    faults at every seam of the compressed-gradient pipeline, plus a
    multi-step soak that tracks one upset across consecutive steps until
    detected / masked / escaped.

    Two specs: single-step coverage of all four seams (the per-seam
    detection/escape/divergence table), then the ``steps``-deep soak over
    the stateful seams (payload transport + error feedback + moments) with
    a transient-vs-persistent sweep — the per-step detection-latency
    histogram lands in the artifact's soak columns.
    """
    n = samples or (6 if quick else 20)
    soak_steps = 4 if quick else 8
    single = CampaignSpec(
        name="train-seams",
        targets=TRAINING_TARGETS,
        fault_models=("bitflip",),
        bit_bands=("significant",) if quick else ("significant", "low"),
        dtypes=("int8", "float32"),
        samples=n, clean_samples=2, seed=seed,
        measure_overhead=True)
    soak = CampaignSpec(
        name="train-soak",
        targets=("train_payload", "train_moments"),
        fault_models=("bitflip",),
        bit_bands=("significant",),
        dtypes=("int8", "float32"),
        samples=max(4, n // 2), clean_samples=2, seed=seed,
        steps=soak_steps, persistent=(False, True))
    return [single, soak]


#: the mesh seams (repro.campaign.targets_training): one shard's payload
#: in transit + the post-reduction summed payload
MULTIDEVICE_TARGETS = ("train_payload_shard", "train_reduced")


def multidevice_specs(seed: int = 0, quick: bool = False,
                      samples: int = 0,
                      shards: int = 4) -> List[CampaignSpec]:
    """Mesh-sharded campaign execution (ROADMAP items): training soaks
    run under shard_map over a fake ``data`` axis of ``shards`` host
    devices, so ``checked_psum`` verifies a REAL collective on every
    step instead of the ``axis_name=None`` fallback every other grid
    exercises.

    Two specs: the mesh seams at full shard count — a single-shard int8
    payload flip that only the post-psum additivity check can see
    (detected after the collective, never before) and the summed payload
    after verification (the post-reduction escape window) — then the
    shard-contrast soak sweeping ``train_payload`` over ``mesh=(1,
    shards)`` so the artifact holds the same seam with and without a
    real reduction in the loop (Ma et al.: fault outcomes shift once
    distributed reductions are real).

    Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the
    CLI forces it for this grid when ``--device-count`` is absent); a
    host with fewer devices degrades per cell with a warning and records
    ``collective_verified=False``.
    """
    n = samples or (4 if quick else 12)
    soak_steps = 2 if quick else 4
    seams = CampaignSpec(
        name="multidevice-seams",
        targets=MULTIDEVICE_TARGETS,
        fault_models=("bitflip",),
        bit_bands=("significant",),
        dtypes=("int8", "int32"),
        samples=n, clean_samples=2, seed=seed,
        steps=soak_steps, mesh=(shards,))
    contrast = CampaignSpec(
        name="multidevice-contrast",
        targets=("train_payload",),
        fault_models=("bitflip",),
        bit_bands=("significant",),
        dtypes=("int8",),
        samples=n, clean_samples=2, seed=seed,
        steps=soak_steps, mesh=(1, shards))
    return [seams, contrast]


def soak_specs(seed: int = 0) -> List[CampaignSpec]:
    """Full-model decode sweep plus a multi-step decode soak.

    ``decode_step`` now runs the ``soak`` protocol, so the second spec
    holds one upset across ``steps`` consecutive decode steps —
    transient (strike once, watch the KV-cache residue) vs persistent
    (flipped weight left in place) — and the per-step detection-latency
    histogram lands in the artifact's soak columns.  The single-step
    spec keeps ``steps=1`` and therefore the baseline cell ids/seeds."""
    single = CampaignSpec(
        name="soak",
        targets=("decode_step",),
        fault_models=("bitflip", "random_value"),
        bit_bands=("all", "significant", "low"),
        samples=16, clean_samples=8, seed=seed,
        measure_overhead=True)
    multi = CampaignSpec(
        name="decode-soak",
        targets=("decode_step",),
        fault_models=("bitflip",),
        bit_bands=("significant",),
        samples=8, clean_samples=2, seed=seed,
        steps=4, persistent=(False, True))
    return [single, multi]


def full_specs(seed: int = 0) -> List[CampaignSpec]:
    kv = CampaignSpec(
        name="kv-sweep",
        targets=("kv_cache",),
        fault_models=("bitflip", "random_value"),
        bit_bands=("all", "low", "significant", "exponent"),
        dtypes=("int8", "float32"),
        samples=400, seed=seed, measure_overhead=True)
    return paper_specs(seed) + [kv] + soak_specs(seed) \
        + training_specs(seed) + multidevice_specs(seed)


GRIDS: Dict[str, object] = {
    "quick": quick_specs,
    "paper": paper_specs,
    "thresholds": thresholds_specs,
    "pallas": pallas_specs,
    "soak": soak_specs,
    "victims": victims_specs,
    "training": training_specs,
    "multidevice": multidevice_specs,
    "full": full_specs,
}
