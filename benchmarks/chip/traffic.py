"""The one traffic generator: reads a traffic file, makes requests from a seed.

A traffic file (``traffic/<name>.json``) holds parameters only:

- ``kind``: ``"dlrm_lookup"`` — one-shot recommendation lookups;
- ``batch``: samples per request, or ``"config"`` for the configuration's
  own ``batch``;
- ``pooling``: ``{"law": "fixed", "value": n}`` — every bag holds n rows
  (1 is one-hot);
- ``ids``: ``{"law": "uniform"}`` — row ids uniform over each table;
- ``dense``: ``{"law": "normal"}`` — dense features N(0, 1);
- ``arrival``: ``{"law": "closed"}`` (one outstanding request; the next is
  sent when the last returns), ``{"law": "poisson", "rate_rps": r}`` or
  ``{"law": "bursty", "rate_rps": r, "burst": k, "spread_s": s}``.

Request i is drawn from (seed, i) when it is asked for, so no request of a
window repeats another and any one can be drawn again for the reference.
Every seed gets the same sizes; only the drawn values differ.  The payload
layout is the serving engine's: ``dense [B, n_dense]`` f32 and ``bags
[n_tables, B, pool]`` int32.
"""
from __future__ import annotations

import dataclasses
import numpy as np

ARRIVALS = ("closed", "poisson", "bursty")
POOLING = ("fixed",)


def batch_of(traffic: dict, cfg: dict) -> int:
    b = traffic["batch"]
    return int(cfg["batch"] if b == "config" else b)


def pool_of(traffic: dict) -> int:
    """Rows in each bag."""
    law = traffic["pooling"]
    if law["law"] == "fixed":
        return int(law["value"])
    raise ValueError(f"unknown pooling law {law['law']!r}; have {POOLING}")


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    """A generator for one use of the seed (any non-negative integers)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), *map(int, tags)]))


@dataclasses.dataclass
class Stream:
    """A seeded request stream: ``payload(i)`` and ``due_s(n)``."""
    batch: int
    pool: int
    n_dense: int
    n_tables: int
    rows: int
    arrival: dict
    seed: int

    def payload(self, i: int) -> dict:
        """Request i's features, drawn from (seed, i)."""
        rng = rng_for(self.seed, 0xD12A, i)
        return {"dense": rng.standard_normal((self.batch, self.n_dense),
                                             dtype=np.float32),
                "bags": rng.integers(0, self.rows,
                                     (self.n_tables, self.batch, self.pool),
                                     dtype=np.int32)}

    def due_s(self, n: int) -> np.ndarray | None:
        """Offsets (s) from the window's start at which requests 0..n-1 are
        due, or None for a closed loop."""
        law = self.arrival["law"]
        if law == "closed":
            return None
        rng = rng_for(self.seed, 0xA771)
        rate = float(self.arrival["rate_rps"])
        if law == "poisson":
            return np.cumsum(rng.exponential(1.0 / rate, n))
        if law == "bursty":
            k = int(self.arrival.get("burst", 8))
            spread = float(self.arrival.get("spread_s", 1e-3))
            n_b = -(-n // k)
            starts = np.cumsum(rng.exponential(k / rate, n_b))
            t = starts[:, None] + rng.uniform(0.0, spread, (n_b, k))
            return np.sort(t.reshape(-1)[:n])
        raise ValueError(f"unknown arrival law {law!r}; have {ARRIVALS}")


def make_stream(traffic: dict, cfg: dict, seed: int) -> Stream:
    """The stream a traffic file and a configuration give for ``seed``."""
    if traffic["kind"] != "dlrm_lookup":
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    if traffic["arrival"]["law"] not in ARRIVALS:
        raise ValueError(f"unknown arrival law {traffic['arrival']['law']!r};"
                         f" have {ARRIVALS}")
    if traffic["ids"]["law"] != "uniform":
        raise ValueError(f"unknown id law {traffic['ids']['law']!r}")
    if traffic["dense"]["law"] != "normal":
        raise ValueError(f"unknown dense law {traffic['dense']['law']!r}")
    return Stream(batch=batch_of(traffic, cfg), pool=pool_of(traffic),
                  n_dense=int(cfg["n_dense"]), n_tables=int(cfg["n_tables"]),
                  rows=int(cfg["table_rows"]),
                  arrival=dict(traffic["arrival"]), seed=int(seed))
