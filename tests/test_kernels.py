"""Per-kernel allclose vs the pure-jnp oracle (ref.py), swept over shapes
and dtypes, in Pallas interpret mode (the TPU-target kernels run on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def tol(dtype):
    return ATOL[dtype]


# ============================================================ flash attention
@pytest.mark.parametrize("b,h,kv,s,d", [
    (1, 4, 4, 128, 64),      # MHA
    (2, 8, 2, 256, 64),      # GQA 4x
    (1, 4, 1, 128, 128),     # MQA, wide head
    (2, 36 // 6, 2, 192, 64),  # non-pow2 seq/heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, h, kv, s, d, dtype):
    k0 = jax.random.PRNGKey(0)
    q = jax.random.normal(k0, (b, h, s, d), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(k0, 1),
                          (b, kv, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(k0, 2),
                          (b, kv, s, d), jnp.float32).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64,
                              interpret=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol(dtype))


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_sliding_window(window):
    b, h, kv, s, d = 1, 4, 2, 256, 64
    k0 = jax.random.PRNGKey(3)
    q = jax.random.normal(k0, (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (b, kv, s, d))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (b, kv, s, d))
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              bq=64, bk=64, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_flash_attention_non_causal():
    b, h, kv, s, d = 1, 2, 2, 128, 64
    k0 = jax.random.PRNGKey(4)
    q = jax.random.normal(k0, (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (b, kv, s, d))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (b, kv, s, d))
    out = ops.flash_attention(q, k, v, causal=False, bq=64, bk=64,
                              interpret=True)
    want = ref.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ============================================================ decode attention
@pytest.mark.parametrize("b,h,kv,t,d,pos", [
    (2, 4, 2, 256, 64, 100),
    (1, 8, 1, 512, 128, 511),   # full cache
    (4, 4, 4, 128, 64, 0),      # first token
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, h, kv, t, d, pos, dtype):
    k0 = jax.random.PRNGKey(1)
    q = jax.random.normal(k0, (b, 1, h, d), jnp.float32).astype(dtype)
    kc = jax.random.normal(jax.random.fold_in(k0, 1),
                           (b, t, kv, d), jnp.float32).astype(dtype)
    vc = jax.random.normal(jax.random.fold_in(k0, 2),
                           (b, t, kv, d), jnp.float32).astype(dtype)
    out = ops.decode_attention(q, kc, vc, jnp.int32(pos), bk=64,
                               interpret=True)
    want = ref.decode_attention(q, kc, vc, jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol(dtype))


# ============================================================ SSD chunk
@pytest.mark.parametrize("b,nc,l,h,p,n", [
    (1, 2, 32, 2, 16, 8),
    (2, 4, 64, 4, 32, 16),
    (1, 1, 128, 8, 64, 64),    # mamba2-780m-like chunk
])
def test_ssd_chunk(b, nc, l, h, p, n):
    k0 = jax.random.PRNGKey(2)
    xd = jax.random.normal(k0, (b, nc, l, h, p))
    a = -jnp.abs(jax.random.normal(jax.random.fold_in(k0, 1),
                                   (b, nc, l, h))) * 0.1
    acum = jnp.cumsum(a, axis=2)
    bm = jax.random.normal(jax.random.fold_in(k0, 2), (b, nc, l, n))
    cm = jax.random.normal(jax.random.fold_in(k0, 3), (b, nc, l, n))
    y, st = ops.ssd_chunk(xd, acum, bm, cm, interpret=True)
    y2, st2 = ref.ssd_chunk(xd, acum, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st2), atol=1e-4)


# ================================================================ topn_lp
@pytest.mark.parametrize("b,k", [
    (4, 9),         # fleet-like: tiny K, padding in both dims
    (8, 128),       # exact tile fit
    (5, 130),       # K spills into a second tile
    (33, 40),       # B not a multiple of the row block
])
@pytest.mark.parametrize("equality", [True, False])
def test_topn_lp_kernel_matches_oracle(b, k, equality):
    from repro.kernels import topn_lp as tl
    k0 = jax.random.PRNGKey(b * 100 + k)
    score = jax.random.normal(k0, (b, k), jnp.float32)
    cost = jax.random.uniform(jax.random.fold_in(k0, 1), (b, k), jnp.float32)
    n = jax.random.randint(jax.random.fold_in(k0, 2), (b,), 1, k + 1)
    out = tl.topn_lp(score, cost, n, equality=equality, interpret=True)
    want = ref.topn_lp(score, cost, n, equality=equality)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_topn_lp_kernel_tie_order():
    """Duplicated scores: the kernel's stable tie handling (lower index
    wins) must match the shared rank core exactly."""
    from repro.kernels import topn_lp as tl
    score = jnp.asarray([[0.5, 0.7, 0.5, 0.7, 0.1],
                         [1.0, 1.0, 1.0, 1.0, 1.0]], jnp.float32)
    cost = jnp.asarray([[1.0, 2.0, 4.0, 8.0, 16.0],
                        [1.0, 2.0, 4.0, 8.0, 16.0]], jnp.float32)
    n = jnp.asarray([3, 2], jnp.int32)
    out = tl.topn_lp(score, cost, n, equality=True, interpret=True)
    # row 0: scores rank (0.7@1, 0.7@3, 0.5@0, 0.5@2, ...) -> {1, 3, 0}
    # row 1: all tied -> lowest indices {0, 1}
    np.testing.assert_allclose(np.asarray(out), [11.0, 3.0], atol=1e-6)


# ================================================================ awc_fw
@pytest.mark.parametrize("b,k,g", [
    (4, 9, 25),       # fleet-like: octave ladder over the paper pool
    (8, 128, 4),      # exact tile fit
    (5, 130, 3),      # K spills into a second tile
    (33, 40, 2),      # B not a multiple of the row block
])
def test_awc_fw_kernel_matches_oracle(b, k, g):
    """Fused gradient + λ-probe kernel vs the pure-jnp oracle: gradients
    allclose, probe cost reductions allclose (selection semantics shared
    through core.ranks)."""
    from repro.kernels import awc_fw as ak
    k0 = jax.random.PRNGKey(b * 1000 + k + g)
    z = jax.random.uniform(k0, (b, k), jnp.float32)
    mu = jax.random.uniform(jax.random.fold_in(k0, 1), (b, k), jnp.float32,
                            0.05, 0.99)
    cost = jax.random.uniform(jax.random.fold_in(k0, 2), (b, k), jnp.float32,
                              0.01, 0.6)
    lams = jax.random.uniform(jax.random.fold_in(k0, 3), (b, g), jnp.float32,
                              0.0, 4.0)
    n = jax.random.randint(jax.random.fold_in(k0, 4), (b,), 1, k + 1)
    grad, costs = ak.awc_fw(z, mu, cost, lams, n, interpret=True)
    grad_w, costs_w = ref.awc_fw(z, mu, cost, lams, n)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(grad_w),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(costs), np.asarray(costs_w),
                               atol=1e-4)


def test_awc_fw_kernel_tie_order_and_positivity():
    """Exactly-representable ties: the kernel's stable tie handling and
    inclusive-matroid positivity filter must match the shared rank core."""
    from repro.kernels import awc_fw as ak
    z = jnp.zeros((2, 4), jnp.float32)      # gradient == clipped mu
    mu = jnp.asarray([[0.5, 0.5, 0.25, 0.5],
                      [0.5, 0.25, 0.125, 0.0625]], jnp.float32)
    cost = jnp.asarray([[1.0, 2.0, 1.0, 4.0],
                        [1.0, 1.0, 1.0, 1.0]], jnp.float32)
    lams = jnp.asarray([[0.0, 0.25], [0.0, 0.25]], jnp.float32)
    n = jnp.asarray([3, 2], jnp.int32)
    grad, costs = ak.awc_fw(z, mu, cost, lams, n, interpret=True)
    _, costs_w = ref.awc_fw(z, mu, cost, lams, n)
    np.testing.assert_allclose(np.asarray(costs), np.asarray(costs_w),
                               atol=0)
    # row 0, λ=0.25: scores (0.25, 0, 0, -0.5) -> only arm 0 positive
    assert costs[0, 1] == 1.0


def test_awc_fw_ops_dispatch():
    """`ops.awc_fw` must agree between the Pallas (interpret) and pure-jnp
    dispatch paths."""
    k0 = jax.random.PRNGKey(7)
    z = jax.random.uniform(k0, (5, 9), jnp.float32)
    mu = jax.random.uniform(jax.random.fold_in(k0, 1), (5, 9), jnp.float32,
                            0.05, 0.99)
    cost = jax.random.uniform(jax.random.fold_in(k0, 2), (5, 9), jnp.float32,
                              0.01, 0.6)
    lams = jnp.broadcast_to(jnp.asarray([0.0, 0.5, 1.0, 8.0]), (5, 4))
    n = jnp.asarray([1, 2, 3, 4, 9], jnp.int32)
    g_plain, c_plain = ops.awc_fw(z, mu, cost, lams, n)
    g_forced, c_forced = ops.awc_fw(z, mu, cost, lams, n, interpret=True)
    np.testing.assert_allclose(np.asarray(g_plain), np.asarray(g_forced),
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(c_plain), np.asarray(c_forced),
                               atol=1e-5)


def test_awc_solve_fused_wide_lowering_matches_reference(tpu_lowering):
    """The AWC relax solve on the fused-kernel wide lowering (awc_fw +
    topn_lp in interpret mode) stays decision-equivalent to the bisect
    reference."""
    from repro.core import relax, rewards as R
    rng = np.random.default_rng(3)
    k, n = 7, 3
    mu = jnp.asarray(rng.uniform(0.05, 0.95, k), jnp.float32)
    c = rng.uniform(0.01, 0.6, k)
    rho = float(np.sort(c)[:n].sum() * 1.6)
    zg = np.array(relax.solve_relaxed("awc", mu, jnp.asarray(c, jnp.float32),
                                      n, rho, engine="grid"))
    zb = np.array(relax.solve_relaxed("awc", mu, jnp.asarray(c, jnp.float32),
                                      n, rho, engine="bisect"))
    vg = float(R.relaxed_reward("awc", jnp.array(zg), mu))
    vb = float(R.relaxed_reward("awc", jnp.array(zb), mu))
    assert vg >= vb - 1e-5, (vg, vb)
    assert float(c @ zg) <= rho * 1.01 + 1e-4


def test_topn_lp_ops_dispatch():
    """`ops.topn_lp` must agree between the Pallas (interpret) and pure-jnp
    dispatch paths."""
    k0 = jax.random.PRNGKey(0)
    score = jax.random.normal(k0, (6, 9), jnp.float32)
    cost = jax.random.uniform(jax.random.fold_in(k0, 1), (6, 9), jnp.float32)
    n = jnp.asarray([1, 2, 3, 4, 5, 9], jnp.int32)
    plain = np.asarray(ops.topn_lp(score, cost, n, equality=True))
    forced = np.asarray(ops.topn_lp(score, cost, n, equality=True,
                                    interpret=True))
    np.testing.assert_allclose(plain, forced, atol=1e-6)


# ===================================================== chunked full-seq SSM
def test_ssd_chunked_matches_sequential_scan():
    """The chunked dual form equals the naive recurrent scan."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n = 1, 64, 2, 8, 4
    k0 = jax.random.PRNGKey(5)
    x = jax.random.normal(k0, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k0, 1),
                                           (b, s, h)))
    a = -jnp.abs(jax.random.normal(jax.random.fold_in(k0, 2), (h,)))
    bmat = jax.random.normal(jax.random.fold_in(k0, 3), (b, s, n))
    cmat = jax.random.normal(jax.random.fold_in(k0, 4), (b, s, n))
    y_chunk, _ = ssd_chunked(x, dt, a, bmat, cmat, chunk=16,
                             return_state=True)

    # naive recurrence: h_t = exp(a dt_t) h_{t-1} + dt_t B_t x_t
    def step(state, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(a * dtt)                             # (h,)
        state = state * decay[:, None, None] + (
            dtt[:, None, None] * xt[:, :, None] * bt[None, None, :])
        y = jnp.einsum("hpn,n->hp", state, ct)
        return state, y

    ys = []
    st = jnp.zeros((h, p, n))
    for t in range(s):
        st, y = step(st, (x[0, t], dt[0, t], bmat[0, t], cmat[0, t]))
        ys.append(y)
    want = jnp.stack(ys)[None]
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(want),
                               atol=2e-4, rtol=2e-3)
