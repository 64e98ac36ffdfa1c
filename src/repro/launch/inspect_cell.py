"""Perf-loop profiler: per-op byte/flop attribution for one dry-run cell.

    PYTHONPATH=src python -m repro.launch.inspect_cell \
        --arch rwkv6-1.6b --shape train_4k --set wkv_chunk=16 --top 25

Compiles the cell like repro.launch.dryrun and prints the top HBM-byte
contributors with their jax-level op_name metadata (trip-multiplied), which
maps each hot spot back to a source line — the "profile" of the dry-run
methodology (no real hardware).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# ruff: noqa: E402
import argparse
import collections
import re

import jax

from repro.launch import dryrun as dr
from repro.launch.costs import HloCostModel, _trip_count, parse_hlo
from repro.launch.mesh import make_production_mesh
from repro.configs.base import SHAPES
from repro.configs.registry import get_arch
from repro.models.base import build_model
from repro.sharding.rules import serve_rules, train_rules

_META_RE = re.compile(r'op_name="([^"]*)"')


def attribute(comps, model, entry):
    by_name_bytes = collections.Counter()
    by_name_flops = collections.Counter()

    def walk(name, mult):
        comp = comps[name]
        for op in comp.ops:
            base = (op.opcode[:-6] if op.opcode.endswith("-start")
                    else op.opcode)
            if base == "while":
                mc = re.search(r"condition=%?([\w.\-]+)", op.attrs)
                mb = re.search(r"body=%?([\w.\-]+)", op.attrs)
                trips = (_trip_count(comps[mc.group(1)])
                         if mc and mc.group(1) in comps else None) or 1
                if mb and mb.group(1) in comps:
                    walk(mb.group(1), mult * trips)
                continue
            c = model.op_cost(op, comp)
            m = _META_RE.search(op.attrs)
            tag = m.group(1) if m else f"<{base}>"
            # strip jit wrapper + uniquifying suffixes for grouping
            tag = re.sub(r"jit\([^)]*\)/", "", tag)
            tag = re.sub(r"\[.*$", "", tag)
            by_name_bytes[tag] += mult * (c.bytes + c.coll_link_bytes)
            by_name_flops[tag] += mult * c.flops
    walk(entry, 1)
    return by_name_bytes, by_name_flops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--set", action="append", default=[], metavar="K=V")
    args = ap.parse_args()

    for kv in args.set:
        k, v = kv.split("=", 1)
        dr.CTX_OVERRIDES[k] = (int(v) if v.lstrip("-").isdigit()
                               else v == "True" if v in ("True", "False")
                               else float(v) if "." in v else v)

    cfg = get_arch(args.arch)
    shape = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    rules = (train_rules(args.multi_pod) if shape.kind == "train"
             else serve_rules(args.multi_pod))
    model = build_model(cfg, max_pos=max(shape.seq_len, 4096)
                        + cfg.meta_tokens + 1)
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            lowered, _ = dr.build_train(model, shape, rules, mesh)
        elif shape.kind == "prefill":
            lowered, _ = dr.build_prefill(model, shape, rules, mesh)
        else:
            lowered, _ = dr.build_decode(model, shape, rules, mesh)
        compiled = lowered.compile()

    comps = parse_hlo(compiled.as_text())
    cm = HloCostModel(comps, mesh.devices.size)
    by_bytes, by_flops = attribute(comps, cm, comps["__entry__"].name)
    total_b = sum(by_bytes.values())
    total_f = sum(by_flops.values())
    print(f"\n== {args.arch} × {args.shape} — per-device totals: "
          f"{total_b/1e9:.1f} GB, {total_f/1e12:.2f} TFLOP ==")
    print(f"{'bytes':>10s} {'%':>5s} {'flops%':>6s}  op_name")
    for tag, b in by_bytes.most_common(args.top):
        print(f"{b/1e9:9.1f}G {100*b/total_b:5.1f} "
              f"{100*by_flops[tag]/max(total_f,1):6.1f}  {tag[:105]}")


if __name__ == "__main__":
    main()
