"""Runner: DLRMv2 (DLRM-DCNv2) lookups served by the system's serving entry.

As ``dlrm_serving``: one tenant on ``default_plan()`` in a
:class:`repro.serving.ServingEngine`, each request one
``engine.run([request], warmup=False)``, the weights the engine's own,
drawn on the device from a model seed that the run's ``--seed`` gives.
What differs:

- the tables differ in rows and pool (the configuration's ``table_rows``
  and ``pooling`` lists), so :func:`stream` draws a request's bags as one
  ``[batch, Σ pooling]`` int32 array of table-local ids, each table's
  columns uniform over its rows;
- a record also carries the step's ``eb_slots`` counter;
- the injected fault flips a bit of a cross-network weight;
- the reference is ``reference/dlrm_dcnv2.py``.
"""
import dataclasses
import importlib.util
from pathlib import Path

import numpy as np

import traffic

_spec = importlib.util.spec_from_file_location(
    "dlrm_serving_base", Path(__file__).with_name("dlrm_serving.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

PROTECTED, UNPROTECTED = base.PROTECTED, base.UNPROTECTED


@dataclasses.dataclass
class Stream:
    """A seeded stream of multi-hot requests: ``payload(i)``, ``due_s(n)``."""
    batch: int
    pools: tuple
    rows: tuple
    n_dense: int
    arrival: dict
    seed: int

    def payload(self, i: int) -> dict:
        """Request i's features, drawn from (seed, i)."""
        rng = traffic.rng_for(self.seed, 0xD12A, i)
        high = np.repeat(np.asarray(self.rows, np.int64), self.pools)
        return {"dense": rng.standard_normal((self.batch, self.n_dense),
                                             dtype=np.float32),
                "bags": rng.integers(0, high, (self.batch, high.size),
                                     dtype=np.int32)}

    due_s = traffic.Stream.due_s


def stream(traffic_: dict, cfg: dict, seed: int) -> Stream:
    """The stream a traffic file (pooling law ``config``: each table's own
    pool from the configuration) and a configuration give for ``seed``."""
    for key, law in (("kind", "dlrm_lookup"), ("ids", "uniform"),
                     ("dense", "normal"), ("pooling", "config")):
        got = traffic_[key] if key == "kind" else traffic_[key]["law"]
        if got != law:
            raise ValueError(f"traffic {key} {got!r}: this runner draws "
                             f"{law!r}")
    if traffic_["arrival"]["law"] not in traffic.ARRIVALS:
        raise ValueError(f"unknown arrival law "
                         f"{traffic_['arrival']['law']!r}")
    return Stream(batch=traffic.batch_of(traffic_, cfg),
                  pools=tuple(int(p) for p in cfg["pooling"]),
                  rows=tuple(int(r) for r in cfg["table_rows"]),
                  n_dense=int(cfg["n_dense"]),
                  arrival=dict(traffic_["arrival"]), seed=int(seed))


def extras(cfg: dict):
    from repro.configs.dlrm import DlrmExtras
    return DlrmExtras(n_dense=int(cfg["n_dense"]),
                      bottom_mlp=tuple(cfg["bottom_mlp"]),
                      n_tables=int(cfg["n_tables"]),
                      table_rows=tuple(int(r) for r in cfg["table_rows"]),
                      emb_dim=int(cfg["emb_dim"]),
                      pooling=tuple(int(p) for p in cfg["pooling"]),
                      top_mlp=tuple(cfg["top_mlp"]),
                      batch=int(cfg["batch"]),
                      interaction=cfg["interaction"],
                      cross_layers=int(cfg["cross_layers"]),
                      cross_rank=int(cfg["cross_rank"]))


class Runner(base.Runner):
    def __init__(self, cfg: dict, stream, seed: int, *, lanes=(PROTECTED,)):
        ex = extras(cfg)            # a program without DLRMv2 stops here
        import jax

        from repro.configs.registry import get_arch
        from repro.protect import default_plan, unprotected_plan
        from repro.serving import ServingEngine, TenantSpec

        self.cfg, self.stream, self.seed = cfg, stream, seed
        self.model_seed = base.model_seed(seed)
        plans = {PROTECTED: default_plan, UNPROTECTED: unprotected_plan}
        tenants = [TenantSpec(lane, plans[lane]()) for lane in lanes]
        self.engine = ServingEngine(get_arch("dlrm"), tenants,
                                    dlrm_extras=ex, seed=self.model_seed)
        jax.block_until_ready(self.engine.params)
        self.engine.warmup(self.request(0))
        for t in tenants:
            for i in range(base.WARM_REQUESTS):
                self.submit(self.request(i, t.name))
        self.engine.reset_state()

    def submit(self, req) -> dict:
        """Serve one request through the engine; -> its record, with the
        step's EmbeddingBag slots."""
        tel = self.engine.run([req], warmup=False)
        rec, steps = tel.requests[0], tel.steps
        return {"aborted": bool(rec.aborted),
                "logits": None if rec.aborted else rec.logits,
                "samples": self.stream.batch,
                "step_s": sum(ev.duration_s for ev in steps),
                "checks": sum(v for ev in steps
                              for k, v in ev.counters.items()
                              if k.endswith("_checks")),
                "eb_slots": sum(ev.counters.get("eb_slots", 0)
                                for ev in steps),
                "flags": sum(ev.errors for ev in steps)}

    def inject_flags(self, i: int) -> int:
        """ABFT flags raised by a request served with one bit of a
        cross-network weight flipped for its step."""
        from repro.serving import FaultInjection
        flip = FaultInjection(step=self.engine.global_step, victim="cross",
                              seed=self.model_seed)
        tel = self.engine.run([self.request(i)], warmup=False, inject=[flip])
        return sum(ev.errors for ev in tel.steps)

    def logit_gap(self, sample: dict, bits=None) -> tuple:
        """-> (widest |served - reference| / rms(reference) over the sampled
        requests, number of logits compared).  With ``bits=4`` the int4
        control stands in for what was served."""
        from reference import dlrm_dcnv2 as ref
        ids = sorted(sample)
        payloads = [self.stream.payload(i) for i in ids]
        dense = np.stack([p["dense"] for p in payloads])
        bags = np.stack([p["bags"] for p in payloads])
        want = ref.logits(self.model_seed, self.cfg, dense, bags)
        got = (np.stack([sample[i] for i in ids]) if bits is None else
               ref.logits(self.model_seed, self.cfg, dense, bags, bits=bits))
        got = np.asarray(got, np.float64).reshape(want.shape)
        scale = float(np.sqrt(np.mean(np.square(want, dtype=np.float64))))
        worst = float(np.max(np.abs(got - want))) if ids else 0.0
        return worst / max(scale, 1e-30), got.size
