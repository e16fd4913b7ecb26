"""Ahead-of-time compiles of the smoke run's kernels for a TPU v5e.

Each test lowers one Pallas entry point at the widths ``chip_smoke.py``
drives (Household: d = 4, the full n = 2,049,280 where the kernel sees the
whole table) and compiles it for one chip of a described ``v5e:2x2``
topology.  No chip is needed: the TPU compiler refuses here what Mosaic
would refuse on the device (64-bit operands, block layouts it cannot tile,
scalar-prefetch tables past SMEM, VMEM overuse).  The topology is built in
a fixture, never at import, so every test worker collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import blocksparse, ops
from repro.kernels.blocksparse import WL_CHUNK, FlatWorklist

N_FULL = 2_049_280          # Household, paper size
N_CHECK = 65_536            # scan check, stream window
D = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A described chip's executables cannot be read back from the
    persistent cache; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def test_fused_sweep_block_sparse_full_size(one_chip):
    """The fit's kernel: count + kept-k over a chunked worklist at the
    paper's n (every chunk reuses this one compiled kernel)."""
    meta = jax.ShapeDtypeStruct((16, 4, WL_CHUNK), jnp.int32,
                                sharding=one_chip)
    lb = jax.ShapeDtypeStruct((16, WL_CHUNK), jnp.float32, sharding=one_chip)

    def fit_sweep(x, m, b):
        wl = FlatWorklist(meta=m, lb=b, n_kept=0, n_total=0)
        return ops.fused_sweep(x, x, 1000.0, interpret=False, worklist=wl)

    _compile(fit_sweep, _f32(one_chip, N_FULL, D), meta, lb)


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_fused_sweep_dense(one_chip, precision):
    _compile(lambda x: ops.fused_sweep(x, x, 1000.0, precision=precision,
                                       interpret=False),
             _f32(one_chip, N_CHECK, D))


def test_count_sweeps(one_chip):
    """Range count and the streaming signed count (window x delta batch)."""
    def counts(w, b, s):
        return (ops.local_density_xy(w, w, 1000.0, interpret=False),
                ops.local_density_delta(w, b, s, 1000.0, interpret=False))

    _compile(counts, _f32(one_chip, N_CHECK, D), _f32(one_chip, 512, D),
             _f32(one_chip, 512))


def test_nn_sweep_full_table(one_chip):
    """Masked NN of a query batch against the fitted table (predict, and
    the fit's unresolved-row fallback)."""
    _compile(lambda q, qk, t, tk: ops.dependent_masked(q, qk, t, tk,
                                                       interpret=False),
             _f32(one_chip, 4096, D), _f32(one_chip, 4096),
             _f32(one_chip, N_FULL, D), _f32(one_chip, N_FULL))


def test_nn_sweep_ring_worklist(one_chip):
    """The fit's unresolved-row fallback under the block-sparse layout: the
    chunked best-1 sweep ((4, 8192) meta, 128 x 256 tiles) over the full
    table."""
    meta = jax.ShapeDtypeStruct((32, 4, WL_CHUNK), jnp.int32,
                                sharding=one_chip)
    lb = jax.ShapeDtypeStruct((32, WL_CHUNK), jnp.float32, sharding=one_chip)

    def fallback(q, qk, t, tk, m, b):
        wl = FlatWorklist(meta=m, lb=b, n_kept=0, n_total=0)
        return ops.dependent_masked(q, qk, t, tk, interpret=False,
                                    worklist=wl)

    _compile(fallback, _f32(one_chip, 4096, D), _f32(one_chip, 4096),
             _f32(one_chip, N_FULL, D), _f32(one_chip, N_FULL), meta, lb)


def test_ring_worklist_build(one_chip):
    """The fallback's ring worklist, built on the device, and its live-pair
    count (plain XLA programs: no kernel to look for)."""
    def build(q, t):
        wl = blocksparse.ring_worklist(q, t, block_n=128, block_m=256)
        assert wl.meta.shape[1:] == (4, WL_CHUNK)
        return wl.meta, blocksparse.ring_live_pairs(
            wl.meta, wl.lb, q[:, 0], bn=128)

    jax.jit(build).lower(_f32(one_chip, 4096, D),
                         _f32(one_chip, N_CHECK, D)).compile()


def test_gather_nn(one_chip):
    """The stream's maxima re-query: rows gathered in-kernel from the
    window table."""
    slots = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one_chip)
    _compile(lambda t, k, s: ops.dependent_masked_gather(t, k, s,
                                                         interpret=False),
             _f32(one_chip, N_CHECK, D), _f32(one_chip, N_CHECK), slots)


N_SHARD = N_FULL // 4       # one of four shards of the distributed fit


def test_shard_count_list_build_and_sweep(one_chip):
    """One shard of the distributed Ex-DPC fit: its d_cut count list built
    on the device (bounds, sort, packing, then the chunked layout at a
    bucketed length) and the fused count + kept-k sweep over it, running
    only the live chunks, against the whole table."""
    def shard(x, t):
        cp = blocksparse.cut_prefix(x, x.shape[0], t, t.shape[0], 1000.0,
                                    bn=256, bm=ops.DENSITY_BLOCK_M)
        wl = blocksparse.cut_worklist(cp, 1000.0, n_chunks=32)
        assert wl.meta.shape == (32, 4, WL_CHUNK)
        return ops.fused_sweep(x, t, 1000.0, interpret=False, worklist=wl)

    _compile(shard, _f32(one_chip, N_SHARD, D), _f32(one_chip, N_FULL, D))


def test_shard_ring_build_and_sweep(one_chip):
    """The shard's fallback: the ring of its unresolved rows (a power-of-two
    pad of 65,536) built on the device, swept best-1 over the live chunks
    only."""
    def fallback(q, qk, t, tk, live):
        wl = blocksparse.ring_worklist(q, t, block_n=256, block_m=256,
                                       ny=t.shape[0] - 3, live_rows=live)
        fd, fp = ops.dependent_masked(q, qk, t, tk, block_n=256,
                                      interpret=False, worklist=wl)
        return fd, fp, blocksparse.ring_live_pairs(
            wl.meta, wl.lb, fd, wl.live_chunks, bn=256)

    live = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _compile(fallback, _f32(one_chip, 65536, D), _f32(one_chip, 65536),
             _f32(one_chip, N_FULL, D), _f32(one_chip, N_FULL), live)
