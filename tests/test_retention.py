"""ops/retention.py: gated power retention of degree 2 from a carried
state. The three forms agree (the attention form written out here, the
chunk form, the one-token recurrence); phi's identity; `valid` leaves
(S, z) after the last real token; a chunk carries into the next; and the
one-token update of a whole pool, the Pallas kernel interpreted, is the
plain one and moves no row it was not given. Tiny sizes, float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from metaflow_tpu.ops import retention

B, T, H, KV, HD = 2, 12, 4, 2, 16
EPS = 1e-6


def inputs(seed=0, b=B, t=T):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    return dict(q=f(b, t, H, HD), k=f(b, t, KV, HD), v=f(b, t, KV, HD),
                # gates of 0.7 to 0.98: the weights reach back over the chunk
                log_g=jax.nn.log_sigmoid(f(b, t, KV) + 2.5))


def empty(b=B):
    D = retention.state_dim(HD)
    return jnp.zeros((b, KV, HD, D)), jnp.zeros((b, KV, D))


def attention_form(q, k, v, log_g):
    """y_t = sum_s a_ts v_s / (sum_s a_ts + eps),
    a_ts = exp(sum_{l=s+1..t} log g_l) (q_t . k_s)^2 / Hd, in float64."""
    q, k, v, log_g = (np.asarray(a, np.float64) for a in (q, k, v, log_g))
    b, t = q.shape[:2]
    out = np.zeros((b, t, H, HD))
    c = np.cumsum(log_g, axis=1)
    for h in range(H):
        kv = h // (H // KV)
        for i in range(t):
            a = np.exp(c[:, i, None, kv] - c[:, :i + 1, kv]) * np.einsum(
                "bd,bsd->bs", q[:, i, h], k[:, :i + 1, kv]) ** 2 / HD
            out[:, i, h] = np.einsum("bs,bse->be", a, v[:, :i + 1, kv]) / (
                a.sum(-1, keepdims=True) + EPS)
    return out


@pytest.mark.parametrize("head_dim", [16, 128])
def test_phi_is_the_square_of_the_dot_product(head_dim):
    r = np.random.default_rng(1)
    a, b = (jnp.asarray(r.normal(size=(5, head_dim)), jnp.float32)
            for _ in range(2))
    pa, pb = retention.phi(a), retention.phi(b)
    want = jnp.sum(a * b, -1) ** 2 / head_dim
    assert np.allclose((pa * pb).sum(-1), want, rtol=1e-5, atol=1e-6)
    # Hd (Hd + 1) / 2 products and Hd / 2 zeros, in whole rows of Hd
    D = retention.state_dim(head_dim)
    assert pa.shape == (5, D) and D == (head_dim // 2 + 1) * head_dim
    _, _, weight = retention._phi_tables(head_dim)
    assert int((weight != 0).sum()) == head_dim * (head_dim + 1) // 2
    assert retention.state_dim(128) == 8320 == 65 * 128


def test_the_three_forms_agree():
    x = inputs()
    want = attention_form(**x)
    y, S, z = retention.chunk(*empty(), **x, eps=EPS)
    assert np.allclose(y, want, atol=2e-5)
    steps, state = [], empty()
    for t in range(T):
        out, *state = retention.step(*state, *(x[n][:, t] for n in
                                               ("q", "k", "v", "log_g")), EPS)
        steps.append(out)
    # the recurrence sums in phi's space, 144 products of four factors a
    # weight, and divides by a sum that a small q . k makes small: float32
    # rounding of a few 1e-6 relative, amplified up to ten times
    assert np.allclose(jnp.stack(steps, 1), want, atol=1e-4)
    assert np.allclose(state[0], S, atol=1e-5)
    assert np.allclose(state[1], z, atol=1e-5)
    assert float(jnp.abs(S).max()) > 0


@pytest.mark.parametrize("cut", [1, 5, 11])
def test_a_chunk_carries_into_the_next(cut):
    x = inputs(seed=2)
    y, S, z = retention.chunk(*empty(), **x, eps=EPS)
    first = {n: a[:, :cut] for n, a in x.items()}
    rest = {n: a[:, cut:] for n, a in x.items()}
    y1, S1, z1 = retention.chunk(*empty(), **first, eps=EPS)
    y2, S2, z2 = retention.chunk(S1, z1, **rest, eps=EPS)
    assert np.allclose(jnp.concatenate([y1, y2], 1), y, atol=2e-5)
    assert np.allclose(S2, S, atol=1e-5) and np.allclose(z2, z, atol=1e-5)


@pytest.mark.parametrize("n_valid", [(12, 12), (5, 9), (0, 12), (1, 0)])
def test_valid_leaves_the_state_after_the_last_real_token(n_valid):
    x = inputs(seed=3)
    _, S0, z0 = retention.chunk(*empty(), **inputs(seed=4), eps=EPS)
    valid = jnp.arange(T)[None] < jnp.asarray(n_valid)[:, None]
    y, S, z = retention.chunk(S0, z0, **x, eps=EPS, valid=valid)
    for row, n in enumerate(n_valid):
        if n == 0:   # nothing real: the state bit for bit
            assert np.array_equal(S[row], S0[row])
            assert np.array_equal(z[row], z0[row])
            continue
        one = {k: a[row:row + 1, :n] for k, a in x.items()}
        want_y, want_S, want_z = retention.chunk(
            S0[row:row + 1], z0[row:row + 1], **one, eps=EPS)
        assert np.allclose(S[row], want_S[0], atol=1e-5)
        assert np.allclose(z[row], want_z[0], atol=1e-5)
        assert np.allclose(y[row, :n], want_y[0], atol=2e-5)


def test_one_token_is_a_chunk_of_one_and_a_masked_row_is_held():
    x = inputs(seed=5, t=1)
    _, S0, z0 = retention.chunk(*empty(), **inputs(seed=6), eps=EPS)
    valid = jnp.asarray([True, False])
    y, S, z = retention.step(S0, z0, *(x[n][:, 0] for n in
                                       ("q", "k", "v", "log_g")), EPS, valid)
    y2, S2, z2 = retention.chunk(S0, z0, **x, eps=EPS, valid=valid[:, None])
    assert np.allclose(y[0], y2[0, 0], atol=2e-5)
    assert np.allclose(S, S2, atol=1e-5) and np.allclose(z, z2, atol=1e-5)
    assert np.array_equal(S[1], S0[1]) and np.array_equal(z[1], z0[1])
    assert not np.allclose(S[0], S0[0])


@pytest.mark.parametrize("valid", [(True, True, True), (True, False, True),
                                   (False, True, False),
                                   (False, False, False)])
def test_the_pools_kernel_is_the_plain_update_and_moves_nothing_else(valid):
    """`_update_state_kernel` interpreted against `_update_state_xla`:
    the layer's valid lanes as updated, every other lane and layer bit
    for bit (an empty list of lanes among them)."""
    r = np.random.default_rng(7)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    D = retention.state_dim(HD)
    pool = f(3, 3, KV, HD, D)
    q, k, v = f(3, H, HD), f(3, KV, HD), f(3, KV, HD)
    log_g = jax.nn.log_sigmoid(f(3, KV))
    valid = jnp.asarray(valid)
    terms = retention._step_terms(q, k, log_g, valid)
    want_pool, want = retention._update_state_xla(pool, 1, *terms, v, valid)
    got_pool, got = retention._update_state_kernel(
        pool, jnp.int32(1), *terms, v, valid, interpret=True)
    mask = np.asarray(valid)
    pool, got_pool, got, want = map(np.asarray, (pool, got_pool, got, want))
    assert np.allclose(got_pool, want_pool, atol=1e-5)
    assert np.allclose(got[mask], want[mask], atol=1e-4)
    assert np.array_equal(got[~mask], np.zeros_like(got[~mask]))
    assert np.array_equal(got_pool[[0, 2]], pool[[0, 2]])
    assert np.array_equal(got_pool[1][~mask], pool[1][~mask])
    if mask.any():
        assert not np.allclose(got_pool[1][mask], pool[1][mask])


def test_update_pool_is_step_on_one_layer_of_the_pools():
    x = inputs(seed=8, b=3, t=1)
    r = np.random.default_rng(9)
    D = retention.state_dim(HD)
    pool_s = jnp.asarray(r.normal(size=(2, 3, KV, HD, D)), jnp.float32)
    pool_z = jnp.asarray(np.abs(r.normal(size=(2, 3, KV, D))), jnp.float32)
    valid = jnp.asarray([True, True, False])
    args = [x[n][:, 0] for n in ("q", "k", "v", "log_g")]
    y, new_s, new_z = jax.jit(retention.update_pool)(
        pool_s, pool_z, 1, *args, EPS, valid)
    want_y, want_S, want_z = retention.step(pool_s[1], pool_z[1], *args, EPS,
                                            valid)
    assert np.allclose(y[:2], want_y[:2], atol=2e-5)
    assert np.allclose(new_s[1], want_S, atol=1e-6)
    assert np.allclose(new_z[1], want_z, atol=1e-6)
    assert np.array_equal(new_s[0], pool_s[0])
    assert np.array_equal(new_s[1, 2], pool_s[1, 2])
    assert np.array_equal(new_z[1, 2], pool_z[1, 2])
