"""SSM chunked scan + MoE dispatch correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.moe import moe_apply, moe_init
from repro.models.ssm import (causal_conv1d, chunked_linear_scan,
                              mamba1_apply, mamba1_init, mamba2_apply,
                              mamba2_init)


def naive_scan(a, b, h0):
    hs = []
    h = h0
    for t in range(a.shape[1]):
        h = a[:, t] * h + b[:, t]
        hs.append(h)
    return jnp.stack(hs, 1), h


@pytest.mark.parametrize("S,chunk", [(16, 4), (17, 4), (64, 64), (5, 8)])
def test_chunked_scan_matches_naive(S, chunk):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.uniform(0.5, 1.0, (2, S, 3, 4)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((2, S, 3, 4)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((2, 3, 4)), jnp.float32)
    h_seq, h_last = chunked_linear_scan(a, b, h0, chunk)
    ref_seq, ref_last = naive_scan(a, b, h0)
    assert float(jnp.abs(h_seq - ref_seq).max()) < 1e-5
    assert float(jnp.abs(h_last - ref_last).max()) < 1e-5


def test_causal_conv_matches_numpy():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 20, 3)).astype(np.float32)
    w = rng.standard_normal((4, 3)).astype(np.float32)
    b = rng.standard_normal(3).astype(np.float32)
    out, carry = causal_conv1d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
    xp = np.pad(x, ((0, 0), (3, 0), (0, 0)))
    ref = np.zeros_like(x)
    for t in range(20):
        ref[:, t] = (xp[:, t:t + 4] * w[None]).sum(1) + b
    assert np.abs(np.asarray(out) - ref).max() < 1e-5
    assert np.allclose(np.asarray(carry), x[:, -3:])


@pytest.mark.parametrize("version", [1, 2])
def test_mamba_streaming_equals_full(version):
    """Running the block on a full sequence == chunked prefix + per-token
    decode with state carry (the SSM cache-correctness invariant)."""
    rng = np.random.default_rng(2)
    d, S = 16, 12
    key = jax.random.key(0)
    if version == 1:
        p = mamba1_init(key, d, d_state=4, expand=2, conv=4,
                        dtype=jnp.float32)
        apply = lambda x, st=None, rs=False: mamba1_apply(
            p, x, d_state=4, chunk=4, state=st, return_state=rs)
    else:
        p = mamba2_init(key, d, d_state=4, expand=2, conv=4, head_dim=8,
                        dtype=jnp.float32)
        apply = lambda x, st=None, rs=False: mamba2_apply(
            p, x, d_state=4, head_dim=8, chunk=4, state=st, return_state=rs)
    x = jnp.asarray(rng.standard_normal((2, S, d)), jnp.float32)
    full = apply(x)
    _, st = apply(x[:, :7], rs=True)
    outs = []
    for t in range(7, S):
        y, st = apply(x[:, t:t + 1], st=st, rs=True)
        outs.append(y)
    tail = jnp.concatenate(outs, 1)
    assert float(jnp.abs(tail - full[:, 7:]).max()) < 1e-4


def _decaying_mamba2(key, d, *, d_state, head_dim):
    """f32 Mamba-2 params whose heads decay at different, non-trivial
    rates (the init's A = -1, dt ~ 0.01 barely decays)."""
    p = mamba2_init(key, d, d_state=d_state, expand=2, conv=4,
                    head_dim=head_dim, dtype=jnp.float32)
    H = p["A_log"].shape[0]
    p["A_log"] = jnp.log(jnp.linspace(0.5, 4.0, H, dtype=jnp.float32))
    p["dt_bias"] = jnp.linspace(-3.0, 0.5, H, dtype=jnp.float32)
    return p


@pytest.mark.parametrize("S,chunk", [(8, 4), (13, 4), (16, 8), (13, 8),
                                     (5, 8)])
def test_mamba2_ssd_prefill_equals_recurrence(S, chunk):
    """The chunked SSD prefill equals the per-token recurrence (a loop of
    S == 1 calls) from a non-zero state, whole and partial chunks."""
    rng = np.random.default_rng(6)
    d, N, P = 16, 4, 8
    p = _decaying_mamba2(jax.random.key(5), d, d_state=N, head_dim=P)
    H = 2 * d // P
    x = jnp.asarray(rng.standard_normal((2, S, d)), jnp.float32)
    st0 = (jnp.asarray(rng.standard_normal((2, 3, 2 * d + 2 * N)),
                       jnp.float32),
           jnp.asarray(rng.standard_normal((2, H, P, N)), jnp.float32))
    apply = lambda x, st: mamba2_apply(p, x, d_state=N, head_dim=P,
                                       chunk=chunk, state=st,
                                       return_state=True)
    y, (conv, h) = apply(x, st0)
    outs, st = [], st0
    for t in range(S):
        y_t, st = apply(x[:, t:t + 1], st)
        outs.append(y_t)
    ref = jnp.concatenate(outs, 1)
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(y - ref).max()) <= 1e-5 * scale
    assert float(jnp.abs(h - st[1]).max()) <= 1e-5 * float(
        jnp.abs(st[1]).max())
    assert np.array_equal(np.asarray(conv), np.asarray(st[0]))


def test_mamba2_prefill_has_no_state_expansion():
    """No intermediate of the compiled Mamba-2 prefill holds a
    (chunk, H, P, N) per-token state expansion: the SSD body works on
    (Q x Q) and (P x N) matmuls."""
    d, N, P, chunk = 12, 5, 6, 8
    p = mamba2_init(jax.random.key(6), d, d_state=N, expand=2, conv=4,
                    head_dim=P, dtype=jnp.float32)
    H = 2 * d // P
    x = jnp.ones((2, 3 * chunk, d), jnp.float32)
    hlo = jax.jit(lambda p, x: mamba2_apply(
        p, x, d_state=N, head_dim=P, chunk=chunk)).lower(p, x).compile(
        ).as_text()
    assert "selective_scan_kernel" in hlo
    assert f"{chunk},{H},{P},{N}]" not in hlo


def _falcon_mamba1(key, d, *, d_state):
    """f32 Mamba-1 params whose channels step at different rates (the
    init's dt ~ 0.01 barely decays)."""
    p = mamba1_init(key, d, d_state=d_state, expand=2, conv=4,
                    dtype=jnp.float32)
    p["dt_bias"] = jnp.linspace(-3.0, 0.5, 2 * d, dtype=jnp.float32)
    return p


@pytest.mark.parametrize("S,chunk", [(8, 4), (13, 4), (16, 8), (13, 8),
                                     (5, 8)])
def test_mamba1_normed_prefill_equals_recurrence(S, chunk):
    """With Falcon-Mamba's Δ/B/C norms, the chunked prefill equals a
    loop of S == 1 calls from a non-zero state, for y, h and the conv
    carry, over whole and partial chunks."""
    rng = np.random.default_rng(7)
    d, N = 16, 4
    p = _falcon_mamba1(jax.random.key(8), d, d_state=N)
    x = jnp.asarray(rng.standard_normal((2, S, d)), jnp.float32)
    st0 = (jnp.asarray(rng.standard_normal((2, 3, 2 * d)), jnp.float32),
           jnp.asarray(rng.standard_normal((2, 2 * d, N)), jnp.float32))
    apply = lambda x, st: mamba1_apply(p, x, d_state=N, chunk=chunk,
                                       state=st, return_state=True)
    y, (conv, h) = apply(x, st0)
    outs, st = [], st0
    for t in range(S):
        y_t, st = apply(x[:, t:t + 1], st)
        outs.append(y_t)
    ref = jnp.concatenate(outs, 1)
    # f32 throughout; the associative scan multiplies the decays in
    # another order than the loop, a few ulps apart
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(y - ref).max()) <= 1e-5 * scale
    assert float(jnp.abs(h - st[1]).max()) <= 1e-5 * float(
        jnp.abs(st[1]).max())
    assert np.array_equal(np.asarray(conv), np.asarray(st[0]))
    # Δ_low, B and C are normed: scaling x_proj, which makes all three,
    # moves y only through the norms' epsilon, at most 1.3 % of its scale
    # here (Δ_low is d // 16 = 1 wide, so x·rsqrt(x² + eps) feels eps
    # where x is near 0); without the norms y moves 5-20 times its scale
    big = dict(p, x_proj=7.0 * p["x_proj"])
    y7 = mamba1_apply(big, x, d_state=N, chunk=chunk, state=st0)
    assert float(jnp.abs(y7 - y).max()) <= 0.05 * scale


def test_mamba1_normed_chunk_resume_is_bitwise():
    """A Mamba-1 prefill with the Δ/B/C norms split at a multiple of the scan chunk,
    the state carried between the calls, equals the unsplit prefill bit
    for bit (chunked serving prefill relies on it)."""
    rng = np.random.default_rng(9)
    d, N, chunk = 16, 4, 4
    p = _falcon_mamba1(jax.random.key(10), d, d_state=N)
    x = jnp.asarray(rng.standard_normal((2, 20, d)), jnp.float32)
    apply = jax.jit(lambda x, st: mamba1_apply(
        p, x, d_state=N, chunk=chunk, state=st, return_state=True))
    st0 = (jnp.zeros((2, 3, 2 * d), jnp.float32),
           jnp.zeros((2, 2 * d, N), jnp.float32))
    y, (conv, h) = apply(x, st0)
    y1, st1 = apply(x[:, :8], st0)
    y2, (conv2, h2) = apply(x[:, 8:], st1)
    assert np.array_equal(np.asarray(jnp.concatenate([y1, y2], 1)),
                          np.asarray(y))
    assert np.array_equal(np.asarray(h2), np.asarray(h))
    assert np.array_equal(np.asarray(conv2), np.asarray(conv))


@pytest.mark.parametrize("version", [1, 2])
def test_mamba_gradients_flow(version):
    if version == 1:  # through Falcon-Mamba's Δ/B/C norms
        p = _falcon_mamba1(jax.random.key(1), 8, d_state=4)
        apply = lambda p, x: mamba1_apply(p, x, d_state=4, chunk=4)
    else:
        p = _decaying_mamba2(jax.random.key(1), 8, d_state=4, head_dim=4)
        apply = lambda p, x: mamba2_apply(p, x, d_state=4, head_dim=4,
                                          chunk=4)
    x = jnp.ones((1, 16, 8), jnp.float32)

    def loss(p):
        return jnp.sum(apply(p, x) ** 2)

    g = jax.grad(loss)(p)
    norms = [float(jnp.abs(v).sum()) for v in jax.tree.leaves(g)]
    assert all(np.isfinite(norms)) and sum(norms) > 0


# ---------------------------------------------------------------- MoE
def dense_moe_oracle(p, x, k):
    T, d = x.shape[1] * x.shape[0], x.shape[2]
    xf = x.reshape(-1, d)
    probs = jax.nn.softmax(xf.astype(jnp.float32) @ p["router"])
    w, i = jax.lax.top_k(probs, k)
    w = w / w.sum(-1, keepdims=True)
    outs = []
    for e in range(p["w_gate"].shape[0]):
        h = jax.nn.silu(xf @ p["w_gate"][e]) * (xf @ p["w_up"][e])
        outs.append(h @ p["w_down"][e])
    ys = jnp.stack(outs, 1)
    sel = jnp.take_along_axis(ys, i[..., None], axis=1)
    out = (sel * w[..., None].astype(ys.dtype)).sum(1).reshape(x.shape)
    if "shared" in p:
        sp = p["shared"]
        h = jax.nn.silu(xf @ sp["w_gate"]) * (xf @ sp["w_up"])
        out = out + (h @ sp["w_down"]).reshape(x.shape)
    return out


@pytest.mark.parametrize("n_shared", [0, 2])
def test_moe_matches_dense_oracle(n_shared):
    rng = np.random.default_rng(3)
    p = moe_init(jax.random.key(2), 32, n_experts=8, moe_d_ff=16,
                 n_shared=n_shared, dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 24, 32)), jnp.float32)
    out, aux = moe_apply(p, x, top_k=2, capacity_factor=8.0)  # no drops
    ref = dense_moe_oracle(p, x, 2)
    assert float(jnp.abs(out - ref).max()) < 1e-5
    assert float(aux["dropped_frac"]) == 0.0
    assert 0.5 < float(aux["aux_loss"]) < 8.0  # ~1 when balanced


def test_moe_capacity_drops_tokens():
    rng = np.random.default_rng(4)
    p = moe_init(jax.random.key(3), 16, n_experts=4, moe_d_ff=8,
                 n_shared=0, dtype=jnp.float32)
    # force imbalance: all tokens identical -> same expert
    x = jnp.ones((1, 64, 16), jnp.float32)
    out, aux = moe_apply(p, x, top_k=1, capacity_factor=0.5)
    assert float(aux["dropped_frac"]) > 0.3


def test_moe_token_independence():
    """Per-token outputs must not depend on other tokens in the batch
    (regression test for the sorted-weight indexing bug)."""
    rng = np.random.default_rng(5)
    p = moe_init(jax.random.key(4), 16, n_experts=4, moe_d_ff=8,
                 n_shared=0, dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 24, 16)), jnp.float32)
    y_full, _ = moe_apply(p, x, top_k=2, capacity_factor=8.0)
    y_head, _ = moe_apply(p, x[:, :10], top_k=2, capacity_factor=8.0)
    assert float(jnp.abs(y_full[:, :10] - y_head).max()) < 1e-6
