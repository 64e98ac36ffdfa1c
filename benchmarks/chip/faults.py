"""Faults planted in the timed path, to show that ``correct`` fails them.

``plant(name)`` patches the served DLRM forward while the context is open;
an engine built inside it serves the broken path:

- ``logit_altered``: one answer of each batch moved by the batch's largest
  |logit|, where it is produced;
- ``flags_silenced``: the ABFT checks never fire, so an injected flip goes
  unseen;
- ``eb_row_shifted``: in every bag of every table the first row id is off
  by one, as if the EmbeddingBag read the neighbouring row;
- ``eb_slot_dropped``: every bag's last row is left out of its sum.
"""
from __future__ import annotations

import contextlib

FAULTS = ("logit_altered", "flags_silenced", "eb_row_shifted",
          "eb_slot_dropped")


@contextlib.contextmanager
def plant(name: str):
    import jax.numpy as jnp

    import repro.models.dlrm as dlrm

    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")
    real_fwd, real_eb = dlrm.dlrm_forward, dlrm.embedding_bag_fwd

    def forward(*a, **kw):
        logit, rep = real_fwd(*a, **kw)
        if name == "logit_altered":
            return logit.at[0].add(jnp.max(jnp.abs(logit))), rep
        return logit, type(rep)(
            checks=rep.checks,
            errors={k: 0 * v for k, v in rep.errors.items()},
            retries=rep.retries, corrections=rep.corrections)

    def embedding_bag(p, idx, ctx, *a, **kw):
        # idx [bags, pool] of one table
        if name == "eb_row_shifted":
            rows = p["table"].shape[0]
            idx = idx.at[:, 0].set((idx[:, 0] + 1) % rows)
        else:
            idx = idx.at[:, -1].set(-1)
        return real_eb(p, idx, ctx, *a, **kw)

    try:
        if name.startswith("eb_"):
            dlrm.embedding_bag_fwd = embedding_bag
        else:
            dlrm.dlrm_forward = forward
        yield
    finally:
        dlrm.dlrm_forward, dlrm.embedding_bag_fwd = real_fwd, real_eb
