"""Per-Pallas-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(lowered for the CPU, the kernel bodies run in the Pallas interpreter)."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.harmonize.ops import harmonize
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.window_agg.ops import window_agg


# ---------------------------------------------------------------- window_agg
@pytest.mark.parametrize("E,S,T", [(1, 1, 8), (2, 5, 24), (4, 8, 128),
                                   (3, 3, 17)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_window_agg_shapes(E, S, T, dtype, rng):
    v = rng.normal(5, 2, (E, S, T)).astype(dtype)
    m = rng.rand(E, S, T) > 0.3
    mu = rng.normal(5, 1, (E, S)).astype(dtype)
    var = np.abs(rng.normal(2, 0.5, (E, S))).astype(dtype) + 0.1
    s1, sp1 = window_agg(v, m, mu, var, use_pallas=True)
    s2, sp2 = window_agg(v, m, mu, var, use_pallas=False)
    assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)
    assert (np.asarray(sp1) == np.asarray(sp2)).all()


@given(st.integers(0, 2**16), st.integers(1, 4), st.integers(1, 6),
       st.integers(2, 40))
@settings(max_examples=15, deadline=None)
def test_window_agg_property(seed, E, S, T):
    rng = np.random.RandomState(seed)
    v = rng.normal(0, 10, (E, S, T)).astype(np.float32)
    m = rng.rand(E, S, T) > rng.uniform(0, 0.9)
    mu = rng.normal(0, 1, (E, S)).astype(np.float32)
    var = np.abs(rng.normal(1, 0.3, (E, S))).astype(np.float32) + 0.05
    s1, sp1 = window_agg(v, m, mu, var, use_pallas=True)
    s2, sp2 = window_agg(v, m, mu, var, use_pallas=False)
    assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)
    assert (np.asarray(sp1) == np.asarray(sp2)).all()


# ----------------------------------------------------------------- harmonize
@pytest.mark.parametrize("E,S,M,T", [(1, 1, 4, 8), (2, 4, 32, 16),
                                     (3, 2, 64, 32), (1, 7, 9, 5)])
def test_harmonize_shapes(E, S, M, T, rng):
    ts = rng.uniform(0, T * 60, (E, S, M)).astype(np.float32)
    vals = rng.normal(0, 1, (E, S, M)).astype(np.float32)
    valid = rng.rand(E, S, M) > 0.2
    ws = np.zeros((E,), np.float32)
    o1, ob1 = harmonize(vals, ts, valid, ws, tick_s=60.0, n_ticks=T,
                        use_pallas=True)
    o2, ob2 = harmonize(vals, ts, valid, ws, tick_s=60.0, n_ticks=T,
                        use_pallas=False)
    assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4, atol=1e-5)
    assert (np.asarray(ob1) == np.asarray(ob2)).all()


@given(st.integers(0, 2**16))
@settings(max_examples=15, deadline=None)
def test_harmonize_property(seed):
    rng = np.random.RandomState(seed)
    E, S = rng.randint(1, 4), rng.randint(1, 5)
    M, T = rng.randint(1, 48), rng.randint(1, 24)
    ts = rng.uniform(-100, (T + 2) * 30, (E, S, M)).astype(np.float32)
    vals = rng.normal(0, 5, (E, S, M)).astype(np.float32)
    valid = rng.rand(E, S, M) > 0.5
    ws = rng.uniform(-50, 50, (E,)).astype(np.float32)
    o1, ob1 = harmonize(vals, ts, valid, ws, tick_s=30.0, n_ticks=T,
                        use_pallas=True)
    o2, ob2 = harmonize(vals, ts, valid, ws, tick_s=30.0, n_ticks=T,
                        use_pallas=False)
    assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4, atol=1e-5)
    assert (np.asarray(ob1) == np.asarray(ob2)).all()


# ---------------------------------------------------------------- rglru_scan
@pytest.mark.parametrize("B,T,W", [(1, 4, 16), (2, 12, 200), (3, 33, 128),
                                   (1, 64, 384)])
def test_rglru_scan_shapes(B, T, W, rng):
    a = rng.uniform(0.5, 0.999, (B, T, W)).astype(np.float32)
    b = rng.normal(0, 0.2, (B, T, W)).astype(np.float32)
    h0 = rng.normal(0, 1, (B, W)).astype(np.float32)
    o1, h1 = rglru_scan(a, b, h0, use_pallas=True)
    o2, h2 = rglru_scan(a, b, h0, use_pallas=False)
    assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)
    assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-5)


def test_rglru_scan_matches_model_impl(rng):
    """Kernel result == the model's associative_scan implementation."""
    from repro.models.rglru import rglru_scan as assoc_scan
    B, T, W = 2, 16, 128
    a = rng.uniform(0.6, 0.99, (B, T, W)).astype(np.float32)
    b = rng.normal(0, 0.1, (B, T, W)).astype(np.float32)
    h0 = np.zeros((B, W), np.float32)
    o1, _ = rglru_scan(a, b, h0, use_pallas=True)
    o2 = assoc_scan(jnp.asarray(a), jnp.asarray(b))
    assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------- flash_attention
@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (1, 128, 2, 1, 32),    # MQA
    (2, 256, 4, 2, 32),    # GQA
    (1, 128, 4, 4, 64),    # MHA
])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (64, 0.0), (0, 50.0)])
def test_flash_attention_sweep(B, S, H, Hkv, D, window, softcap, rng):
    q = rng.normal(0, 1, (B, S, H, D)).astype(np.float32)
    k = rng.normal(0, 1, (B, S, Hkv, D)).astype(np.float32)
    v = rng.normal(0, 1, (B, S, Hkv, D)).astype(np.float32)
    o1 = flash_attention(q, k, v, window=window, softcap=softcap,
                         use_pallas=True, q_blk=64, kv_blk=64)
    o2 = flash_attention(q, k, v, window=window, softcap=softcap,
                         use_pallas=False)
    assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16(rng):
    B, S, H, Hkv, D = 1, 128, 2, 1, 32
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.bfloat16)
    o1 = flash_attention(q, k, v, use_pallas=True, q_blk=64, kv_blk=64)
    o2 = flash_attention(q, k, v, use_pallas=False)
    assert_allclose(np.asarray(o1, dtype=np.float32),
                    np.asarray(o2, dtype=np.float32), rtol=5e-2, atol=5e-2)


def test_flash_attention_matches_model_blockwise(rng):
    """Kernel == the model's jnp blockwise attention (same recurrence)."""
    from repro.models.layers import blockwise_attention
    B, S, Hkv, G, D = 1, 128, 2, 2, 16
    q = rng.normal(0, 1, (B, S, Hkv, G, D)).astype(np.float32)
    k = rng.normal(0, 1, (B, S, Hkv, D)).astype(np.float32)
    v = rng.normal(0, 1, (B, S, Hkv, D)).astype(np.float32)
    pos = np.broadcast_to(np.arange(S), (B, S)).astype(np.int32)
    out_model = blockwise_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        q_positions=jnp.asarray(pos), kv_positions=jnp.asarray(pos),
        kv_valid=jnp.ones((B, S), bool), q_chunk=32, kv_chunk=32)
    # kernel layout: q (B, S, H, D) with H = Hkv*G in (kv, g) order
    qk = q.reshape(B, S, Hkv * G, D)
    out_kernel = flash_attention(qk, k, v, use_pallas=True, q_blk=32,
                                 kv_blk=32)
    assert_allclose(np.asarray(out_model).reshape(B, S, -1),
                    np.asarray(out_kernel).reshape(B, S, -1),
                    rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------- locf
@pytest.mark.parametrize("E,S,T", [(1, 1, 8), (2, 5, 24), (3, 3, 17)])
def test_locf_kernel_shapes(E, S, T, rng):
    from repro.kernels.locf.ops import locf
    v = rng.normal(0, 1, (E, S, T)).astype(np.float32)
    o = rng.rand(E, S, T) > 0.5
    iv = rng.normal(0, 1, (E, S)).astype(np.float32)
    ih = rng.rand(E, S) > 0.5
    o1, h1 = locf(v, o, iv, ih, use_pallas=True)
    o2, h2 = locf(v, o, iv, ih, use_pallas=False)
    assert_allclose(np.asarray(o1)[np.asarray(h1)],
                    np.asarray(o2)[np.asarray(h2)], rtol=1e-6)
    assert (np.asarray(h1) == np.asarray(h2)).all()


def test_locf_kernel_matches_gapfill_module(rng):
    """Kernel == the core gap-fill LOCF (the stage it accelerates)."""
    from repro.core import gapfill as gf
    from repro.kernels.locf.ops import locf
    import jax.numpy as jnp
    E, S, T = 2, 3, 16
    v = rng.normal(0, 1, (E, S, T)).astype(np.float32)
    o = rng.rand(E, S, T) > 0.5
    state = gf.init_state(E, S)
    want_v, want_h = gf.locf(jnp.asarray(v), jnp.asarray(o), state)
    got_v, got_h = locf(v, o, np.zeros((E, S), np.float32),
                        np.zeros((E, S), bool), use_pallas=True)
    assert (np.asarray(got_h) == np.asarray(want_h)).all()
    assert_allclose(np.asarray(got_v)[np.asarray(got_h)],
                    np.asarray(want_v)[np.asarray(want_h)], rtol=1e-6)
