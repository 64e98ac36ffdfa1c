"""The benchmark harness: one cell, one run, one result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

- ``configs/<config>.json``: the configuration as it is run; its
  ``runner`` names ``runners/<runner>.py``, which makes the request stream
  from a traffic file (``stream``), builds the system under test
  (``Runner``) and serves one request at a time;
- ``traffic/<traffic>.json``: the traffic mix, read by the runner's
  generator (``traffic.py`` for DLRM lookups);
- ``metrics/<metric>.py``: a per-layer metric's reader, ``read(ctx)`` ->
  a number or None (nothing to read: the metric is left out);
- ``limits/<workload>.json``: the limit of each number that decides
  ``correct`` in that cell.

A run: set-up (the runner builds and warms the system), a measured
window of ``seconds`` with the profiler off, with ``trace`` a traced
segment of each lane after it, then the checks: an injected fault, the
device's peak memory, the program's state freed, the reference compared.
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parents[1]
#: the traced segment of each lane lasts this long (the profiler's window)
TRACE_SECONDS = 2.0
#: where a traced run writes its profile, inside the checkout
TRACE_DIR = CHECKOUT / ".bench_trace"
#: compile events JAX reports once per program it lowers
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",)

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import trace_reduce  # noqa: E402
import work  # noqa: E402


class NoDevice(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(root: Path, kind: str, name: str, ext: str) -> Path:
    """``<kind>/<name><ext>`` under ``root``, else under the benchmark's own
    directory."""
    for base in (Path(root), ROOT):
        path = base / kind / f"{name}{ext}"
        if path.exists():
            return path
    raise FileNotFoundError(f"no {kind}/{name}{ext} under {root} or {ROOT}")


def applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def find_cell(spec: dict, workload: str) -> tuple:
    """-> (cell, config entry) of ``workload`` in the benchmark spec."""
    cells = [w for w in spec["workloads"] if w["name"] == workload]
    if len(cells) != 1:
        raise KeyError(f"no workload {workload!r}; have "
                       f"{[w['name'] for w in spec['workloads']]}")
    cell = cells[0]
    cfgs = [c for c in spec["configs"] if c["name"] == cell["config"]]
    return cell, cfgs[0]


class Compiles:
    """Counts the programs JAX lowers while ``counting`` is on."""

    def __init__(self):
        from jax import monitoring
        self.counting, self.n = False, 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.counting and event in COMPILE_EVENTS:
            self.n += 1


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chip(s); JAX found "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peak_bytes(chips: int):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_cache() -> None:
    import jax

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, goes to the cache, so that
    # only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ------------------------------ the window ---------------------------------

def drive(runner, stream, seconds: float, *, tenant: str, first: int = 0,
          annotate: bool = False, indices=None) -> tuple:
    """Serve requests for ``seconds`` (or exactly ``indices``); -> (records,
    window seconds).  A closed loop sends each request when the last one
    returns; an open loop sends it when due and times it from then."""
    import contextlib

    from jax.profiler import TraceAnnotation
    span = TraceAnnotation if annotate else (
        lambda name: contextlib.nullcontext())
    due = None if indices is not None else stream.due_s(
        int(seconds * 1e5) + 1)
    recs = []
    t_start = time.perf_counter()
    k = 0
    while True:
        if indices is not None:
            if k >= len(indices):
                break
            i = indices[k]
        else:
            i = first + k
            at = (time.perf_counter() - t_start if due is None
                  else float(due[k]))
            if at >= seconds:
                break
        with span("bench/request"):
            with span("bench/build_request"):
                req = runner.request(i, tenant)
            if due is not None and indices is None:
                wait = t_start + float(due[k]) - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                sent = t_start + float(due[k])
            else:
                sent = time.perf_counter()
            with span("bench/engine_run"):
                rec = runner.submit(req)
        rec.update(i=i, tenant=tenant,
                   latency_s=time.perf_counter() - sent)
        recs.append(rec)
        k += 1
    return recs, time.perf_counter() - t_start


def traced_segments(runner, stream, first: int, lanes: list) -> tuple:
    """Each lane traced in turn over the same requests, the first for
    ``TRACE_SECONDS``: -> (trace, {lane: (lo_ns, hi_ns, records)})."""
    import jax
    from jax.profiler import TraceAnnotation

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    recs = {}
    try:
        for lane in lanes:
            with TraceAnnotation(f"bench/lane/{lane}"):
                if lane == lanes[0]:
                    recs[lane], _ = drive(runner, stream, TRACE_SECONDS,
                                          tenant=lane, first=first,
                                          annotate=True)
                else:
                    recs[lane], _ = drive(
                        runner, stream, 0, tenant=lane, annotate=True,
                        indices=[r["i"] for r in recs[lanes[0]]])
    finally:
        jax.profiler.stop_trace()
    tr = trace_reduce.load(trace_reduce.find_xspace(str(TRACE_DIR)))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return tr, {lane: (*trace_reduce.span_range(tr, f"bench/lane/{lane}"),
                       recs[lane]) for lane in lanes}


def log_spread(recs: list, window_s: float, log) -> None:
    """Latency and engine-step quantiles, and requests done in each tenth
    of the window, for reading a run's noise."""
    import numpy as np
    if not recs:
        return
    q = (0.05, 0.25, 0.5, 0.75, 0.95, 0.99, 1.0)
    for key in ("latency_s", "step_s"):
        v = np.quantile([r[key] for r in recs], q) * 1e3
        log(f"{key[:-2]} ms at {q}: " + " ".join(f"{x:.3f}" for x in v))
    done = np.cumsum([r["latency_s"] for r in recs])
    log("requests per tenth of the window: " + " ".join(
        str(int(x)) for x in np.histogram(done, bins=10,
                                          range=(0, done[-1]))[0]))


# ------------------------------ per-layer ----------------------------------

class ReadCtx:
    """What a per-layer reader may read."""

    def __init__(self, *, cfg, stream, peaks, window, window_s, trace,
                 segments, protected, unprotected):
        self.cfg, self.stream, self.peaks = cfg, stream, peaks
        self.window, self.window_s = window, window_s
        self.trace, self.segments = trace, segments
        self.protected, self.unprotected = protected, unprotected
        self.work = work
        self.reduce = trace_reduce

    def device_ops(self) -> list:
        """Ops of the first device plane (one-chip cells), or []."""
        if self.trace is None or not self.trace["ops"]:
            return []
        return self.trace["ops"][sorted(self.trace["ops"])[0]]

    def device_modules(self) -> list:
        if self.trace is None or not self.trace["modules"]:
            return []
        return self.trace["modules"][sorted(self.trace["modules"])[0]]

    def segment(self, lane: str) -> tuple:
        """(lo_ns, hi_ns, records) of a lane's traced segment."""
        return self.segments[lane]

    def per_request_ms(self, lane: str, needle: str):
        """Device ms per request of the ops under ``needle`` in the lane's
        traced segment; None where no such op ran."""
        ops = self.device_ops()
        if not ops or lane not in self.segments:
            return None
        lo, hi, recs = self.segments[lane]
        if not trace_reduce.matching(ops, needle) or not recs:
            return None
        return trace_reduce.op_time_ns(ops, needle, lo, hi) / 1e6 / len(recs)


def readers(entries: list, root: Path = ROOT) -> dict:
    """{metric entry name: (entry, reader module)}."""
    return {m["name"]: (m, load_module(
        find(root, "metrics", m["name"], ".py"),
        f"metric_{m['name'].replace('.', '_')}")) for m in entries}


def read_metrics(mods: dict, ctx: ReadCtx) -> dict:
    out = {}
    for name, (m, mod) in mods.items():
        v = mod.read(ctx)
        if v is not None and math.isfinite(v):
            out[name] = {"value": float(v), "unit": m["unit"]}
    return out


# ------------------------------ checks -------------------------------------

def judge(values: dict, limits: dict) -> dict:
    """{name: {"value", "limit", "pass_if"}} for each number compared."""
    out = {}
    for name, v in values.items():
        lim = limits[name]
        op = lim.get("pass_if", "<=")
        ok = v <= lim["limit"] if op == "<=" else v >= lim["limit"]
        out[name] = {"value": v, "limit": lim["limit"], "pass_if": op,
                     "ok": bool(ok)}
    return out


# ------------------------------ one run ------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool, *,
        spec_path=None, root: Path = ROOT, peaks_path=None, t0=None,
        require_tpu: bool = True, log=None, control_bits=None) -> dict:
    """Run one cell once; -> the result object (the last stdout line).

    ``control_bits`` (calibration only) also judges the control: the
    reference at that many bits in the program's place, on the same
    sample and against the same limits, under the key ``control``."""
    t0 = time.perf_counter() if t0 is None else t0
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    spec = load_json(spec_path or CHECKOUT / "BENCHMARK.json")
    cell, cfg_entry = find_cell(spec, workload)
    cfg = load_json(CHECKOUT / cfg_entry["file"] if spec_path is None
                    else Path(spec_path).parent / cfg_entry["file"])
    traffic = load_json(find(root, "traffic", cell["traffic"], ".json"))
    limits = load_json(find(root, "limits", workload, ".json"))
    runner_mod = load_module(find(root, "runners", cfg["runner"], ".py"),
                             f"runner_{cfg['runner']}")

    device = device_info(int(cell["chips"]), require_tpu)
    peaks = work.load_peaks(peaks_path or root / "peaks.json", device["kind"])
    enable_cache()
    compiles = Compiles()

    # a traced run also builds the lanes its per-layer readers compare
    # with (a reader's ``LANES``)
    mods = readers([m for m in spec["per_layer"] if applies(m, workload)],
                   root) if trace else {}
    lanes = [runner_mod.PROTECTED] + sorted(
        {lane for _, mod in mods.values() for lane in getattr(mod, "LANES",
                                                                ())})
    stream = runner_mod.stream(traffic, cfg, seed)
    runner = runner_mod.Runner(cfg, stream, seed, lanes=lanes)
    setup_s = time.perf_counter() - t0
    log(f"setup_s: {setup_s:.3f}")

    compiles.counting = True
    recs, window_s = drive(runner, stream, seconds,
                           tenant=runner_mod.PROTECTED)
    compiles.counting = False
    log(f"window: {len(recs)} requests in {window_s:.3f} s, "
        f"{compiles.n} compile(s) inside it")
    log_spread(recs, window_s, log)
    print(f"compiles_in_window: {compiles.n}", flush=True)

    tr, segments = None, {}
    if trace:
        tr, segments = traced_segments(runner, stream, len(recs), lanes)

    # the checks: protection, then memory, then the reference
    values = {
        "clean_flags": float(sum(r["flags"] for r in recs)),
        "aborted": float(sum(r["aborted"] for r in recs)),
        "injected_flip_flags": float(runner.inject_flags(
            len(recs) + sum(len(s[2]) for s in segments.values()))),
    }
    device["memory_peak_bytes"] = peak_bytes(int(cell["chips"]))
    sample = runner.sample(recs)
    runner.free()
    gap, n_cmp = runner.logit_gap(sample)
    values["logit_gap"] = gap
    log(f"compared {n_cmp} served logits of {len(sample)} requests")
    checks = judge(values, limits)
    correct = all(c["ok"] for c in checks.values()) and n_cmp > 0
    control = None
    if control_bits is not None:
        # the same comparison, with the control's answers in the program's
        ctl = dict(values, logit_gap=runner.logit_gap(
            sample, bits=control_bits)[0])
        control = {"logit_gap": ctl["logit_gap"], "correct": all(
            c["ok"] for c in judge(ctl, limits).values())}
        log(f"control at {control_bits} bits: logit_gap "
            f"{control['logit_gap']!r}, correct {control['correct']}")
    failed = sum(1 for r in recs if r["aborted"] or r["flags"])

    if trace:
        ctx = ReadCtx(cfg=cfg, stream=stream, peaks=peaks, window=recs,
                      window_s=window_s, trace=tr, segments=segments,
                      protected=runner_mod.PROTECTED,
                      unprotected=runner_mod.UNPROTECTED)
        metrics = read_metrics(mods, ctx)
        ops = ctx.device_ops()
        lo, hi, _ = segments[runner_mod.PROTECTED]
        device["busy_s"] = trace_reduce.busy_ns(ops, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {
            "device_ops": trace_reduce.top_ops(ops, lo, hi),
            "idle_gaps": trace_reduce.idle_gaps(
                tr, ops, lo, hi,
                exclude={f"bench/lane/{runner_mod.PROTECTED}"}),
        }
    else:
        e2e = runner_mod.Runner.end_to_end(recs, window_s)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in spec["end_to_end"] if applies(m, workload)}
        breakdown = None

    result = {"correct": bool(correct), "attempted": len(recs),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control is not None:
        result["control"] = control
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"],
                            "pass_if": c["pass_if"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['pass_if']} "
            f"{c['limit']!r}) {'ok' if c['ok'] else 'FAILED'}")
    return result
