"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attn.kernel import flash_attention_pallas
from repro.kernels.flash_attn.ref import flash_attention_ref
from repro.kernels.intersect.kernel import intersect_pallas
from repro.kernels.intersect.ref import intersect_ref
from repro.kernels.membership.kernel import membership_pallas
from repro.kernels.membership.ref import membership_ref
from repro.kernels.moe_gemm.kernel import moe_gemm_pallas
from repro.kernels.moe_gemm.ref import moe_gemm_ref
from repro.kernels.segment_spmm.ops import segment_spmm_tiled
from repro.kernels.segment_spmm.ref import segment_sum_dense


@pytest.mark.parametrize("B,M,K", [(7, 16, 3), (64, 130, 9), (256, 64, 1),
                                   (3, 257, 17)])
def test_membership_sweep(B, M, K):
    rng = np.random.default_rng(B * M + K)
    rows = np.sort(rng.integers(0, 300, (B, M)).astype(np.int32), axis=1)
    vals = rng.integers(0, 300, (B, K)).astype(np.int32)
    got = membership_pallas(jnp.asarray(rows), jnp.asarray(vals))
    want = membership_ref(jnp.asarray(rows), jnp.asarray(vals))
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("B,M", [(5, 20), (33, 129), (128, 64)])
def test_intersect_sweep(B, M):
    rng = np.random.default_rng(B + M)
    sent = 500
    a = np.sort(rng.integers(0, sent, (B, M)).astype(np.int32), axis=1)
    b = np.sort(rng.integers(0, sent, (B, M)).astype(np.int32), axis=1)
    m1, c1 = intersect_pallas(jnp.asarray(a), jnp.asarray(b), sent)
    m2, c2 = intersect_ref(jnp.asarray(a), jnp.asarray(b), sent)
    assert np.array_equal(np.asarray(m1), np.asarray(m2))
    assert np.array_equal(np.asarray(c1), np.asarray(c2))


@pytest.mark.parametrize("block_b", [None, 8, 16, 64, 512])
@pytest.mark.parametrize("B,M", [(17, 8), (40, 65), (9, 200)])
def test_intersect_tile_parity(B, M, block_b):
    """The exposed block_b tiling kwarg (and the window-width default,
    block_b=None) never changes the result — any batch tile is
    bit-identical to the jnp reference."""
    from repro.kernels.intersect.ops import intersect
    rng = np.random.default_rng(B * M + (block_b or 0))
    sent = 300
    a = np.sort(rng.integers(0, sent, (B, M)).astype(np.int32), axis=1)
    b = np.sort(rng.integers(0, sent, (B, M)).astype(np.int32), axis=1)
    m1, c1 = intersect(jnp.asarray(a), jnp.asarray(b), sent,
                       use_kernel=True, interpret=True, block_b=block_b)
    m2, c2 = intersect(jnp.asarray(a), jnp.asarray(b), sent,
                       use_kernel=False)
    assert np.array_equal(np.asarray(m1), np.asarray(m2))
    assert np.array_equal(np.asarray(c1), np.asarray(c2))


def test_intersect_tile_defaults():
    """Lane chunks are always 128 wide (narrow windows are padded, never
    narrowed); the batch tile shrinks as the window widens so the resident
    row block keeps fitting VMEM."""
    from repro.kernels.intersect.ops import tile_defaults
    assert tile_defaults(8) == (512, 128)
    assert tile_defaults(1) == (512, 128)
    assert tile_defaults(200) == (512, 128)
    assert tile_defaults(1796) == (256, 128)
    assert tile_defaults(1 << 14) == (32, 128)


@pytest.mark.parametrize("B,M", [(3, 16), (7, 130), (260, 64), (1, 300)])
def test_varint_delta_vlen_sweep(B, M):
    """The fused delta+LEB128-size Pallas kernel (the wire-codec fast
    path) matches the jnp reference over sorted-with-holes id lanes."""
    from repro.kernels.varint.kernel import delta_vlen_pallas
    from repro.kernels.varint.ref import delta_vlen_ref
    rng = np.random.default_rng(B * M)
    n = 1 << 27
    ids = np.full((B, M), n, np.int32)
    for r in range(B):
        k = int(rng.integers(0, M + 1))
        vals = np.sort(rng.choice(n, size=k, replace=False)).astype(np.int32)
        ids[r, np.sort(rng.choice(M, k, replace=False))] = vals
    d1, v1 = delta_vlen_pallas(jnp.asarray(ids), n)
    d2, v2 = delta_vlen_ref(jnp.asarray(ids), n)
    assert np.array_equal(np.asarray(d1), np.asarray(d2))
    assert np.array_equal(np.asarray(v1), np.asarray(v2))


@pytest.mark.parametrize("E,N,D,tn,te", [(300, 50, 8, 16, 64),
                                         (1000, 128, 32, 32, 128),
                                         (64, 7, 4, 8, 32)])
def test_segment_spmm_sweep(E, N, D, tn, te):
    rng = np.random.default_rng(E + N)
    msgs = jnp.asarray(rng.normal(size=(E, D)).astype(np.float32))
    dst = rng.integers(0, N, E).astype(np.int32)
    got = segment_spmm_tiled(msgs, dst, N, tn=tn, te=te, use_kernel=True)
    want = segment_sum_dense(msgs, jnp.asarray(dst), N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("S,H,Hk,D", [(64, 4, 2, 32), (128, 2, 2, 16)])
def test_flash_attention_sweep(S, H, Hk, D, dtype, rtol):
    key = jax.random.PRNGKey(S + H)
    ks = jax.random.split(key, 3)
    BH = 3
    q = jax.random.normal(ks[0], (BH, S, D), dtype)
    k = jax.random.normal(ks[1], (BH, S, D), dtype)
    v = jax.random.normal(ks[2], (BH, S, D), dtype)
    got = flash_attention_pallas(q, k, v, causal=True, bq=32, bk=32)
    want = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=rtol)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("E,C,d,f", [(4, 64, 32, 64), (2, 128, 16, 128)])
def test_moe_gemm_sweep(E, C, d, f, dtype, rtol):
    key = jax.random.PRNGKey(E * C)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (E, C, d), dtype)
    wg = (jax.random.normal(ks[1], (E, d, f), dtype) * 0.1).astype(dtype)
    wu = (jax.random.normal(ks[2], (E, d, f), dtype) * 0.1).astype(dtype)
    wd = (jax.random.normal(ks[3], (E, f, d), dtype) * 0.1).astype(dtype)
    got = moe_gemm_pallas(x, wg, wu, wd, bc=32, bf=32)
    want = moe_gemm_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=rtol)


def test_flash_matches_model_reference():
    """The in-model pure-JAX flash (models.layers.flash_attention) and the
    Pallas kernel agree — kernel swap-in safety."""
    from repro.models.layers import flash_attention as model_flash
    key = jax.random.PRNGKey(0)
    B, S, H, Hk, D = 2, 64, 4, 2, 16
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hk, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hk, D))
    a = model_flash(q, k, v, causal=True, q_chunk=32, kv_chunk=32)
    from repro.kernels.flash_attn.ops import flash_attention_k
    b = flash_attention_k(q, k, v, causal=True, use_kernel=True, bq=32, bk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
