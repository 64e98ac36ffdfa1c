from repro.sharding.logical import (
    LogicalParam,
    is_lp,
    param,
    values_of,
    spec_for,
    specs_of,
    shardings_of,
    like_shardings,
    constrain,
)
from repro.sharding.rules import Rules, train_rules, serve_rules, batch_axes

import jax as _jax


def shard_map(f, *, mesh, in_specs, out_specs, manual_axes=None):
    """``jax.shard_map`` over ``manual_axes`` (default: every mesh axis),
    with replication checking off (our steps psum explicitly)."""
    manual = set(manual_axes) if manual_axes is not None \
        else set(mesh.axis_names)
    return _jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False,
                          axis_names=manual)


def make_data_mesh(n_shards: int, devices=None):
    """One-axis ``("data",)`` mesh of ``n_shards`` host devices — the fake
    data-parallel axis campaign soaks :func:`shard_map` over so
    ``checked_psum`` verifies a real collective.  ``devices`` selects an
    explicit slice (cell placement); default is the front of the host
    platform."""
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh((n_shards,), ("data",), devices=devices)


__all__ = [
    "LogicalParam", "is_lp", "param", "values_of",
    "spec_for", "specs_of", "shardings_of", "like_shardings", "constrain",
    "Rules", "train_rules", "serve_rules", "batch_axes", "shard_map",
    "make_data_mesh",
]
