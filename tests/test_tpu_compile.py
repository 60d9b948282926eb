"""Main-path Pallas kernels compile for a TPU v5e.

Each case lowers one ``*_pallas`` entry point with ``interpret=False``
against a described (not attached) ``v5e:2x2`` topology and compiles it
with the TPU compiler, at n = 2^20 columns and m ∈ {1, 8, 64} queries,
and the windowed arena verify also at the review corpus's two sealed
segments: what Mosaic refuses (lane blocks that are not a multiple of
128 nor the whole axis, lane gathers beyond one vreg, blocks past the
scoped VMEM limit) fails here instead of on the chip.  Nothing runs, so results are covered by the
interpret-mode tests, not by this file.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and
every test worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.hamming_kernel import (DEFAULT_BLOCK_M, DEFAULT_BLOCK_N,
                                          exact_rerank_pallas,
                                          hamming_distances_pallas,
                                          sparse_verify_arena_packed_pallas,
                                          sparse_verify_arena_pallas,
                                          sparse_verify_batch_pallas)

N = 1 << 20
T_ROOTS = 4097          # ℓ_s roots of a few segments + the delta slot
REVIEW_T = 9_252_432    # ℓ_s roots of the review corpus's two segments


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler, or its library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _windows(sharding, n):
    """(start, steps) specs of one window plan over n columns."""
    nb = n // DEFAULT_BLOCK_N
    return (_spec(sharding, (nb,), jnp.int32),
            _spec(sharding, (nb,), jnp.int32))


def _compile_checked(fn, args, kwargs):
    compiled = fn.lower(*args, interpret=False, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [1, 8, 64])
def test_arena_packed_verify_compiles_review(one_chip, m):
    """Suffix-layout verify at the review corpus's L=16, b=2."""
    b, S = 2, 8
    u32, i32 = jnp.uint32, jnp.int32
    args = (_spec(one_chip, (N,), u32), _spec(one_chip, (m,), u32),
            _spec(one_chip, (m, T_ROOTS), i32), _spec(one_chip, (N,), i32),
            _spec(one_chip, (N,), i32), _windows(one_chip, N))
    _compile_checked(sparse_verify_arena_packed_pallas, args,
                     dict(b=b, S=S, tau=2, block_m=min(DEFAULT_BLOCK_M, m)))


@pytest.mark.parametrize("m", [1, 2, 4])
@pytest.mark.parametrize("n,S", [(8_388_608, 4), (4_194_304, 5)])
def test_windowed_verify_compiles_review_segments(one_chip, n, S, m):
    """The windowed packed verify at the review corpus's sealed
    segments (ℓ_s = 12 and 11 of L = 16) over its whole root plane, at
    the scheduler's batch buckets up to ``max_batch`` 4."""
    u32, i32 = jnp.uint32, jnp.int32
    args = (_spec(one_chip, (n,), u32), _spec(one_chip, (m,), u32),
            _spec(one_chip, (m, REVIEW_T), i32), _spec(one_chip, (n,), i32),
            _spec(one_chip, (n,), i32), _windows(one_chip, n))
    _compile_checked(sparse_verify_arena_packed_pallas, args,
                     dict(b=2, S=S, tau=3, block_m=m))


@pytest.mark.parametrize("m", [1, 8, 64])
def test_arena_verify_compiles_gist(one_chip, m):
    """Full-layout arena verify at gist's L=64, b=8 (W=2 words)."""
    b, W = 8, 2
    args = (_spec(one_chip, (b, W, N), jnp.uint32),
            _spec(one_chip, (b, W, m), jnp.uint32),
            _spec(one_chip, (m, T_ROOTS), jnp.int32),
            _spec(one_chip, (N,), jnp.int32),
            _spec(one_chip, (N,), jnp.int32), _windows(one_chip, N))
    _compile_checked(sparse_verify_arena_pallas, args,
                     dict(tau=9, block_m=min(DEFAULT_BLOCK_M, m)))


@pytest.mark.parametrize("m", [1, 8, 64])
def test_batch_verify_compiles_sift(one_chip, m):
    """Dense-base batched verify at sift's L=32, b=4 (W=1)."""
    b, W = 4, 1
    args = (_spec(one_chip, (b, W, N), jnp.uint32),
            _spec(one_chip, (b, W, m), jnp.uint32),
            _spec(one_chip, (m, N), jnp.int32))
    _compile_checked(sparse_verify_batch_pallas, args,
                     dict(tau=5, block_m=min(DEFAULT_BLOCK_M, m)))


@pytest.mark.parametrize("m", [1, 8, 64])
def test_delta_scan_compiles(one_chip, m):
    """The delta buffer's full-length scan at the review's L=16, b=2."""
    b, W = 2, 1
    args = (_spec(one_chip, (b, W, N), jnp.uint32),
            _spec(one_chip, (b, W, m), jnp.uint32))
    _compile_checked(hamming_distances_pallas, args,
                     dict(block_m=min(DEFAULT_BLOCK_M, m)))


@pytest.mark.parametrize("m", [1, 8, 64])
def test_exact_rerank_compiles_default_vocab(one_chip, m):
    """Re-rank at the default 256-token vocabulary (Wp = 8 words)."""
    Wp = 8
    args = (_spec(one_chip, (Wp, N), jnp.uint32),
            _spec(one_chip, (Wp, m), jnp.uint32),
            _spec(one_chip, (m, N), jnp.int32))
    _compile_checked(exact_rerank_pallas, args,
                     dict(metric="jaccard", block_m=min(DEFAULT_BLOCK_M, m)))
