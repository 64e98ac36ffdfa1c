"""``python -m repro.campaign`` — run a resilience campaign, or diff two
campaign artifacts.

Examples::

    python -m repro.campaign --quick
    python -m repro.campaign --grid paper --seed 7
    python -m repro.campaign --grid thresholds        # EB rel_bound sweep
    python -m repro.campaign --grid pallas --quick    # fused-kernel parity
    python -m repro.campaign --grid victims           # decode victim sweep
    python -m repro.campaign --grid training --quick  # train-step seams
    python -m repro.campaign --grid multidevice --quick  # sharded cells
    python -m repro.campaign --grid serving_soak --quick   # live-traffic
    python -m repro.campaign --grid adaptive --quick  # threshold loop
    python -m repro.campaign --grid full --device-count 8 --out bench/
    python -m repro.campaign --diff OLD.json NEW.json # exit 1 on regression
    python -m repro.campaign --trend                  # baseline history gate
    python -m repro.campaign --trend BASE.json ... NEW.json
    python -m repro.campaign --quick --obs-dir obs/   # event/trace export
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.campaign",
        description="Declarative fault-injection sweeps with batched "
                    "execution, JSON artifacts, and a cross-PR differ.")
    ap.add_argument("--quick", action="store_true",
                    help="shorthand for --grid quick (the CI smoke grid)")
    ap.add_argument("--grid", default=None,
                    choices=["quick", "paper", "thresholds", "pallas",
                             "soak", "victims", "training", "multidevice",
                             "serving_soak", "paging", "adaptive",
                             "full"],
                    help="named grid to run (see repro.campaign.grids; "
                         "serving_soak runs repro.serving.soak, paging "
                         "runs repro.serving.paging_soak, adaptive runs "
                         "repro.campaign.adaptive)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan", default=None,
                    help="serving grids: override every tenant's "
                         "protection plan — compact string, or "
                         "@path.json holding a plan dict")
    ap.add_argument("--samples", type=int, default=0,
                    help="override the per-cell sample count "
                         "(quick / thresholds grids)")
    ap.add_argument("--out", default=".",
                    help="artifact directory (default: cwd)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="trials per compiled vmap chunk")
    ap.add_argument("--device-count", type=int, default=0,
                    help="fake host devices (XLA_FLAGS) to pmap across")
    ap.add_argument("--diff", nargs=2, metavar=("OLD", "NEW"),
                    help="diff two BENCH_campaign_*.json artifacts and "
                         "exit 1 on detection/FP regressions")
    ap.add_argument("--det-tol", type=float, default=0.02,
                    help="--diff: allowed detection-rate drop")
    ap.add_argument("--fp-tol", type=float, default=0.02,
                    help="--diff: allowed false-positive-rate rise")
    ap.add_argument("--overhead-tol", type=float, default=None,
                    help="--diff: allowed overhead rise (opt-in — "
                         "wall-clock noise on shared runners)")
    ap.add_argument("--diff-out", default=None,
                    help="--diff: also write the markdown report here")
    ap.add_argument("--trend", nargs="*", metavar="ARTIFACT", default=None,
                    help="fold artifacts (oldest..newest) into a per-cell "
                         "history table and gate the newest against the "
                         "prior median; with no paths, uses the committed "
                         "benchmarks/baselines/BENCH_campaign_*.json; "
                         "exits 1 on trend regressions")
    ap.add_argument("--trend-out", default=None,
                    help="--trend: also write the markdown history here")
    ap.add_argument("--latency-tol", type=float, default=None,
                    help="--trend: allowed overhead rise vs the prior "
                         "median (opt-in — wall-clock noise)")
    ap.add_argument("--obs-dir", default=None,
                    help="export observability artifacts (fault-event "
                         "JSONL, Chrome trace, Prometheus text) for the "
                         "run into this directory")
    ap.add_argument("--obs-flush-every", type=int, default=0, metavar="N",
                    help="crash-durable obs: append events to the JSONL "
                         "as they happen and rewrite metric/trace "
                         "snapshots every N events (needs --obs-dir) — "
                         "a killed soak keeps everything flushed so far")
    ap.add_argument("--monitor", action="store_true",
                    help="attach the live detection-health monitor to "
                         "the obs bus (windowed alert rules + per-scope "
                         "health states; summary printed at the end)")
    args = ap.parse_args(argv)

    if args.diff:
        from repro.campaign.diff import run_diff
        return run_diff(args.diff[0], args.diff[1], det_tol=args.det_tol,
                        fp_tol=args.fp_tol,
                        overhead_tol=args.overhead_tol,
                        out_path=args.diff_out)
    if args.trend is not None:
        from repro.campaign.trend import run_trend
        return run_trend(args.trend, det_tol=args.det_tol,
                         fp_tol=args.fp_tol,
                         latency_tol=args.latency_tol,
                         out_path=args.trend_out)

    grid = args.grid or ("quick" if args.quick else None)
    if grid is None:
        ap.error("pick a grid (--quick / --grid {quick,paper,thresholds,"
                 "pallas,soak,victims,training,multidevice,serving_soak,"
                 "paging,adaptive,full}) or --diff OLD NEW")

    # grids with sharded cells are pointless on a 1-device host: force
    # the 4-device host platform the multidevice baseline was produced
    # on unless the caller chose a count themselves (full includes the
    # multidevice specs).  Say so: the split platform also hosts the
    # grid's overhead timings, which must not silently change regime.
    if grid in ("multidevice", "full") and not args.device_count:
        args.device_count = 4
        print(f"[{grid}] forcing --device-count 4 for the sharded cells "
              f"(overhead timings run on the split host platform; pass "
              f"--device-count to override)", flush=True)
    if args.device_count:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.device_count}"
        ).strip()

    # jax import happens after XLA_FLAGS is set
    from repro.campaign.executor import (CHUNK, resolve_device_count,
                                         run_campaign)
    from repro.campaign.grids import (GRIDS, multidevice_specs,
                                      pallas_specs, paper_specs,
                                      quick_specs, thresholds_specs,
                                      training_specs, victims_specs)
    from repro.compile_cache import enable_compile_cache

    # warns and falls back when the flag landed after jax initialized
    resolve_device_count(args.device_count or None)
    enable_compile_cache()

    obs = None
    if args.obs_dir or args.monitor:
        from repro.obs import Observability
        obs = Observability.create()
        if args.obs_dir and args.obs_flush_every > 0:
            obs.open_incremental(args.obs_dir,
                                 every=args.obs_flush_every)
    monitor = None
    if args.monitor:
        from repro.obs import Monitor
        monitor = Monitor()

    if grid == "serving_soak":
        # live-traffic soak: the serving engine, not the vmapped executor
        import dataclasses

        from repro.campaign.artifacts import markdown_table
        from repro.serving.soak import (full_soak_spec, quick_soak_spec,
                                        run_soak_campaign)
        spec = None
        if args.plan is not None:
            spec = quick_soak_spec(args.seed) if args.quick \
                else full_soak_spec(args.seed)
            spec = dataclasses.replace(spec, tenants=tuple(
                (n, w, args.plan) for n, w, _ in spec.tenants))
        result = run_soak_campaign(spec, quick=args.quick, seed=args.seed,
                                   out_dir=args.out, obs=obs,
                                   monitor=monitor,
                                   verbose=lambda s: print(s, flush=True))
        print()
        print(markdown_table(result))
        print(f"artifact: "
              f"{os.path.join(args.out, 'BENCH_campaign_serving_soak')}"
              f".json")
        _print_monitor(monitor)
        _write_obs(obs, args.obs_dir)
        return 0
    if grid == "adaptive":
        # controller-convergence cells (repro.campaign.adaptive)
        from repro.campaign.adaptive import run_adaptive_campaign
        from repro.campaign.artifacts import markdown_table
        result = run_adaptive_campaign(quick=args.quick, seed=args.seed,
                                       out_dir=args.out, obs=obs,
                                       verbose=lambda s: print(s,
                                                               flush=True))
        print()
        print(markdown_table(result))
        name = "adaptive_quick" if args.quick else "adaptive"
        print(f"artifact: "
              f"{os.path.join(args.out, 'BENCH_campaign_' + name)}.json")
        _write_obs(obs, args.obs_dir)
        return 0
    if grid == "paging":
        # paged-KV parity + repair cells (repro.serving.paging_soak)
        from repro.campaign.artifacts import markdown_table
        from repro.serving.paging_soak import run_paging_campaign
        result = run_paging_campaign(quick=args.quick, seed=args.seed,
                                     plan=args.plan, out_dir=args.out,
                                     obs=obs,
                                     verbose=lambda s: print(s,
                                                             flush=True))
        print()
        print(markdown_table(result))
        name = "paging_quick" if args.quick else "paging"
        print(f"artifact: "
              f"{os.path.join(args.out, 'BENCH_campaign_' + name)}.json")
        _write_obs(obs, args.obs_dir)
        return 0
    if grid == "quick":
        specs = quick_specs(seed=args.seed, samples=args.samples or 600)
    elif grid == "paper":
        specs = paper_specs(seed=args.seed, quick=args.quick)
    elif grid == "thresholds":
        specs = thresholds_specs(seed=args.seed,
                                 samples=args.samples or 400)
    elif grid == "pallas":
        specs = pallas_specs(seed=args.seed, quick=args.quick,
                             samples=args.samples or 0)
    elif grid == "victims":
        specs = victims_specs(seed=args.seed, samples=args.samples or 12)
    elif grid == "training":
        specs = training_specs(seed=args.seed, quick=args.quick,
                               samples=args.samples or 0)
    elif grid == "multidevice":
        specs = multidevice_specs(seed=args.seed, quick=args.quick,
                                  samples=args.samples or 0)
    else:
        specs = GRIDS[grid](seed=args.seed)

    # quick training/multidevice runs get their own artifact name: the
    # committed CI baselines are the quick variants and must not collide
    # with full runs
    name = f"{grid}_quick" if grid in ("training", "multidevice",
                                       "pallas") \
        and args.quick else grid
    result = run_campaign(name, specs, out_dir=args.out,
                          chunk=args.chunk or CHUNK, obs=obs,
                          monitor=monitor,
                          verbose=lambda s: print(s, flush=True))

    from repro.campaign.artifacts import (breakdown_markdown,
                                          latency_markdown, markdown_table,
                                          threshold_curve_markdown)
    print()
    print(markdown_table(result))
    if grid == "thresholds":
        print(threshold_curve_markdown(result))
    if grid in ("training", "multidevice", "full"):
        print(latency_markdown(result))
    bd = breakdown_markdown(result)
    if bd:
        print(bd)
    print(f"artifact: {os.path.join(args.out, 'BENCH_campaign_' + name)}"
          f".json")
    _print_monitor(monitor)
    _write_obs(obs, args.obs_dir)
    return 0


def _print_monitor(monitor) -> None:
    if monitor is None:
        return
    ms = monitor.summary()
    print(f"monitor: {ms['ticks']} tick(s), {ms['alerts_fired']} "
          f"alert(s), health {ms['health'] or '{}'}")
    for a in ms["alerts"]:
        print(f"  alert {a['rule']} [{a['severity']}] {a['scope']}: "
              f"{a['metric']}={a['value']:.4g} vs {a['threshold']:.4g}")


def _write_obs(obs, obs_dir) -> None:
    if obs is None:
        return
    if obs_dir is None:
        return
    paths = obs.write(obs_dir)
    for kind, path in sorted(paths.items()):
        print(f"obs {kind}: {path}")


if __name__ == "__main__":
    sys.exit(main())
