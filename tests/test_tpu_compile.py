"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles at real widths for a v5e chip
that is described, not attached (``jax.experimental.topologies``), which
raises what Mosaic would raise on the chip — unaligned blocks, VMEM
overuse, operand types the MXU refuses.  Each test asserts that a Mosaic
kernel (``tpu_custom_call``) is in the compiled program.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.dlrm import EXTRAS
from repro.core import LANE

MOSAIC = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no topology"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert MOSAIC in compiled.as_text()
    return compiled


@pytest.mark.parametrize("with_colcheck", [False, True])
@pytest.mark.parametrize("a_dtype", ["int8", "uint8"])
@pytest.mark.parametrize("m,k,n", [(10, 1024, 1024), (2048, 1024, 1024)],
                         ids=["dlrm_top", "prefill"])
def test_abft_qgemm_compiles(one_chip, m, k, n, a_dtype, with_colcheck):
    from repro.kernels.abft_qgemm import abft_qgemm_pallas
    _compile(functools.partial(abft_qgemm_pallas,
                               with_colcheck=with_colcheck),
             _shape(one_chip, (m, k), getattr(jnp, a_dtype)),
             _shape(one_chip, (k, n + LANE), jnp.int8))


@pytest.mark.parametrize("m,n", [(10, 1024), (2048, 1024)])
def test_quantize_rows_compiles(one_chip, m, n):
    from repro.kernels.quantize_rows import quantize_rows_pallas
    _compile(quantize_rows_pallas, _shape(one_chip, (m, n), jnp.float32))


def _eb_args(sharding, lead, rows, d, bags, pool):
    return (_shape(sharding, lead + (rows, d), jnp.int8),
            _shape(sharding, lead + (rows,), jnp.float32),
            _shape(sharding, lead + (rows,), jnp.float32),
            _shape(sharding, lead + (bags, pool), jnp.int32))


@pytest.mark.parametrize("tables,rows,d,bags,pool", [
    (1, EXTRAS.table_rows, EXTRAS.emb_dim, EXTRAS.batch, EXTRAS.pooling),
    (EXTRAS.n_tables, EXTRAS.table_rows, EXTRAS.emb_dim, EXTRAS.batch,
     EXTRAS.pooling),
    # the DLRM repo's synthetic benchmark: 8 x 1M x 64, batch 128
    (8, 1_000_000, 64, 128, 100),
    # Criteo-style one-hot lookups: batch 20, pool 1
    (EXTRAS.n_tables, EXTRAS.table_rows, EXTRAS.emb_dim, 20, 1),
], ids=["one_table", "vmap_tables", "synth64", "onehot20"])
def test_abft_eb_compiles_at_table_geometry(one_chip, tables, rows, d, bags,
                                            pool):
    """Table I: bags 10, pooling 100, 4M x 128 int8 rows per table (and the
    d = 64 and pool-1 geometries the chip benchmark serves).  The tables
    are read in place: their device layout keeps their bytes (a
    row-per-tile layout would take 4x), and no table-sized temporary
    appears.  Only the small per-row and per-slot arrays are tile-padded
    (the [26, rows] f32 scales pad 26 -> 32 rows)."""
    from repro.kernels.abft_embeddingbag import abft_eb_pallas
    fn = functools.partial(abft_eb_pallas, weights=None)
    lead = () if tables == 1 else (tables,)
    if tables > 1:
        fn = jax.vmap(fn)
    args = _eb_args(one_chip, lead, rows, d, bags, pool)
    mem = _compile(fn, *args).memory_analysis()
    in_bytes = sum(math.prod(a.shape) * a.dtype.itemsize for a in args)
    table_bytes = rows * d
    assert in_bytes <= mem.argument_size_in_bytes \
        < in_bytes + tables * table_bytes // 10
    assert mem.temp_size_in_bytes < table_bytes // 100


def test_dlrm_pallas_lane_compiles_at_paper_geometry(one_chip, monkeypatch):
    """The served DLRM forward under the default plan, at the paper's
    geometry (26 x 4M x 128 int8 tables): its GEMMs and EmbeddingBags run
    as Mosaic kernels and it needs no temporaries beyond its arguments."""
    from repro.kernels import ops
    from repro.models.dlrm import dlrm_forward, init_dlrm
    from repro.protect import default_plan, protect
    from repro.sharding import values_of

    # the wrappers ask jax.devices() (the CPU here); steer them to the chip
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ex = EXTRAS
    params = jax.tree.map(
        lambda s: _shape(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: values_of(init_dlrm(
            jax.random.key(0), ex, quant=True, table_rows=ex.table_rows))))
    fwd = protect(functools.partial(dlrm_forward, ex=ex), default_plan(),
                  compute_dtype=jnp.bfloat16)
    compiled = _compile(
        lambda p, d, b: (lambda o: (o[0], o[1].as_metrics()))(fwd(p, d, b)),
        params, _shape(one_chip, (ex.batch, ex.n_dense), jnp.float32),
        _shape(one_chip, (ex.n_tables, ex.batch, ex.pooling), jnp.int32))
    n_kernels = compiled.as_text().count(MOSAIC)
    assert n_kernels > len(ex.bottom_mlp) + len(ex.top_mlp)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
