"""Runner: DLRM lookups served by the system's serving entry.

One tenant on ``default_plan()`` (fused Pallas EmbeddingBag and qgemm,
ABFT on, policy ``log``) in a :class:`repro.serving.ServingEngine`; each
request is one ``engine.run([request], warmup=False)``.  A traced run may
add a tenant on ``unprotected_plan()``, on the same engine and tables, so
the protection's device cost can be read off the trace.

The weights are the engine's own, drawn on the device from a model seed
that the run's ``--seed`` gives.
"""
from __future__ import annotations

import gc

import numpy as np

from traffic import make_stream as stream  # noqa: F401  (the generator)

PROTECTED, UNPROTECTED = "protected", "unprotected"
#: requests served through each lane in set-up, after its compile
WARM_REQUESTS = 3
#: requests whose logits are compared with the reference
SAMPLE = 16


def model_seed(seed: int) -> int:
    """A 31-bit model seed from any run seed (JAX keys keep 32 bits)."""
    return int(np.random.SeedSequence([int(seed), 0x5EED]).generate_state(
        1)[0] & 0x7FFFFFFF)


def extras(cfg: dict):
    from repro.configs.dlrm import DlrmExtras
    return DlrmExtras(n_dense=int(cfg["n_dense"]),
                      bottom_mlp=tuple(cfg["bottom_mlp"]),
                      n_tables=int(cfg["n_tables"]),
                      table_rows=int(cfg["table_rows"]),
                      emb_dim=int(cfg["emb_dim"]),
                      pooling=int(cfg["pooling"]),
                      top_mlp=tuple(cfg["top_mlp"]),
                      batch=int(cfg["batch"]))


class Runner:
    def __init__(self, cfg: dict, stream, seed: int, *, lanes=(PROTECTED,)):
        import jax

        from repro.configs.registry import get_arch
        from repro.protect import default_plan, unprotected_plan
        from repro.serving import ServingEngine, TenantSpec

        self.cfg, self.stream, self.seed = cfg, stream, seed
        self.model_seed = model_seed(seed)
        plans = {PROTECTED: default_plan, UNPROTECTED: unprotected_plan}
        tenants = [TenantSpec(lane, plans[lane]()) for lane in lanes]
        self.engine = ServingEngine(get_arch("dlrm"), tenants,
                                    dlrm_extras=extras(cfg),
                                    seed=self.model_seed)
        jax.block_until_ready(self.engine.params)
        self.engine.warmup(self.request(0))
        for t in tenants:
            for i in range(WARM_REQUESTS):
                self.submit(self.request(i, t.name))
        self.engine.reset_state()

    # ------------------------------ serving --------------------------------

    def request(self, i: int, tenant: str = PROTECTED):
        from repro.serving.workload import Request
        return Request(rid=i, tenant=tenant, arrival_s=0.0, kind="dlrm",
                       prompt_len=0, max_new_tokens=0,
                       payload=self.stream.payload(i))

    def submit(self, req) -> dict:
        """Serve one request through the engine; -> its record."""
        tel = self.engine.run([req], warmup=False)
        rec = tel.requests[0]
        steps = tel.steps
        return {"aborted": bool(rec.aborted),
                "logits": None if rec.aborted else rec.logits,
                "samples": self.stream.batch,
                "step_s": sum(ev.duration_s for ev in steps),
                "checks": sum(v for ev in steps
                              for k, v in ev.counters.items()
                              if k.endswith("_checks")),
                "flags": sum(ev.errors for ev in steps)}

    # ------------------------------ metrics --------------------------------

    @staticmethod
    def end_to_end(recs: list, window_s: float) -> dict:
        lat = [r["latency_s"] for r in recs]
        p95 = float(np.quantile(np.asarray(lat), 0.95, method="linear"))
        done = sum(r["samples"] for r in recs if not r["aborted"])
        return {"lookup_p95_ms": p95 * 1e3,
                "samples_per_s": done / window_s}

    # ------------------------------ correctness ----------------------------

    def inject_flags(self, i: int) -> int:
        """ABFT flags raised by a request served with one bit of a
        bottom-MLP weight flipped for its step."""
        from repro.serving import FaultInjection
        flip = FaultInjection(step=self.engine.global_step, victim="bottom",
                              seed=self.model_seed)
        tel = self.engine.run([self.request(i)], warmup=False, inject=[flip])
        return sum(ev.errors for ev in tel.steps)

    def free(self) -> None:
        """Drop the program's state (tables, weights, compiled lanes)."""
        self.engine.params = None
        self.engine = None
        gc.collect()

    def sample(self, recs: list) -> dict:
        """A sample drawn from the seed of the requests the window
        finished: {request index: served logits}."""
        from traffic import rng_for
        served = {r["i"]: np.asarray(r["logits"], np.float64) for r in recs
                  if not r["aborted"] and r["tenant"] == PROTECTED}
        ids = sorted(served)
        pick = rng_for(self.seed, 0x5A3B).choice(
            len(ids), size=min(SAMPLE, len(ids)), replace=False)
        return {ids[j]: served[ids[j]] for j in sorted(pick)}

    def logit_gap(self, sample: dict, bits=None) -> tuple:
        """-> (widest |served - reference| / rms(reference) over the sampled
        requests, number of logits compared).  With ``bits=4`` the int4
        control stands in for what was served."""
        from reference import dlrm as ref
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
