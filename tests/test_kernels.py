"""Per-kernel validation: Pallas body (interpret mode on CPU) vs pure-jnp
oracle, swept over shapes / b / L / block sizes, plus hypothesis properties."""

import numpy as np
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: deterministic fallback shim
    from _hypothesis_compat import given, settings, st

from repro.core import hamming as H
from repro.kernels import ops, ref
from repro.kernels.hamming_kernel import hamming_distances_pallas, sparse_verify_pallas


def make_db(rng, n, L, b):
    db = rng.integers(0, 1 << b, size=(n, L)).astype(np.uint8)
    planes = H.pack_vertical(db, b)  # (n, b, W)
    vert = np.transpose(planes, (1, 2, 0))  # (b, W, n)
    return db, jnp.asarray(vert)


@pytest.mark.parametrize("b,L", [(2, 16), (2, 32), (4, 32), (8, 64), (1, 8), (4, 100)])
@pytest.mark.parametrize("n,m,block_n", [(256, 3, 128), (512, 1, 512), (130, 2, 128),
                                         (300, 8, 128), (300, 16, 128),
                                         (390, 64, 128)])
def test_hamming_kernel_matches_oracle(b, L, n, m, block_n):
    rng = np.random.default_rng(b * 1000 + L + n)
    db, db_vert = make_db(rng, n, L, b)
    q, q_vert = make_db(rng, m, L, b)
    got = np.asarray(ops.hamming_distances(db_vert, q_vert, block_n=block_n, use_kernel=True))
    want = np.asarray(ref.hamming_distances_ref(db_vert, q_vert))
    np.testing.assert_array_equal(got, want)
    brute = (q[:, None, :] != db[None, :, :]).sum(axis=2)
    np.testing.assert_array_equal(got, brute)


def test_big_sentinel_consistent():
    """The kernel package's pruned-lane sentinel must equal core.bst.BIG."""
    from repro.core.bst import BIG
    from repro.kernels.hamming_kernel import BIG as KBIG
    assert int(BIG) == int(KBIG) == int(ref.BIG)


@pytest.mark.parametrize("b,L,tau", [(2, 16, 2), (4, 32, 5), (8, 64, 3), (2, 16, 0)])
def test_sparse_verify_matches_oracle(b, L, tau):
    rng = np.random.default_rng(b + L + tau)
    n = 384
    db, paths_vert = make_db(rng, n, L, b)
    q, q_vert = make_db(rng, 1, L, b)
    q_vert = q_vert[..., 0]
    base = rng.integers(0, tau + 2, size=n).astype(np.int32)
    got, got_d = ops.sparse_verify(paths_vert, q_vert, jnp.asarray(base),
                                   tau=tau, block_n=128, use_kernel=True)
    want, want_d = ref.sparse_verify_ref(paths_vert, q_vert,
                                         jnp.asarray(base), tau)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    # distances are exact: base + suffix Hamming distance
    suffix = (db != q[0][None]).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(got_d), base + suffix)


def test_kernel_direct_no_padding():
    """Exercise the raw pallas_call (n, m exact multiples of the tiles)."""
    rng = np.random.default_rng(0)
    b, L, n, m = 4, 32, 1024, 4
    _, db_vert = make_db(rng, n, L, b)
    _, q_vert = make_db(rng, m, L, b)
    got = np.asarray(hamming_distances_pallas(db_vert, q_vert, block_m=2,
                                              block_n=256, interpret=True))
    want = np.asarray(ref.hamming_distances_ref(db_vert, q_vert))
    np.testing.assert_array_equal(got, want)


def test_small_path_uses_oracle():
    rng = np.random.default_rng(1)
    _, db_vert = make_db(rng, 10, 16, 2)
    _, q_vert = make_db(rng, 2, 16, 2)
    got = np.asarray(ops.hamming_distances(db_vert, q_vert))  # n < block -> oracle
    want = np.asarray(ref.hamming_distances_ref(db_vert, q_vert))
    np.testing.assert_array_equal(got, want)


def test_kernel_path_refuses_backends_without_a_compiled_kernel(monkeypatch):
    """Interpret mode is the CPU test path only: a host whose accelerator
    failed to start must not serve answers through it silently."""
    rng = np.random.default_rng(2)
    _, db_vert = make_db(rng, 256, 16, 2)
    _, q_vert = make_db(rng, 2, 16, 2)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu' backend"):
        ops.hamming_distances(db_vert, q_vert, block_n=128, use_kernel=True)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 8), st.integers(1, 70), st.integers(1, 300), st.integers(0, 6), st.randoms())
def test_verify_property(b, L, n, tau, rnd):
    rng = np.random.default_rng(rnd.randint(0, 2**31))
    db, paths_vert = make_db(rng, n, L, b)
    q, q_vert = make_db(rng, 1, L, b)
    base = rng.integers(0, 4, size=n).astype(np.int32)
    got, got_d = ops.sparse_verify(paths_vert, q_vert[..., 0], jnp.asarray(base),
                                   tau=tau, block_n=128)
    suffix = (db != q[0][None]).sum(axis=1)
    want = ((base + suffix) <= tau).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(got_d), base + suffix)
