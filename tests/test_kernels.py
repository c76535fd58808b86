"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.cin import cin_layer
from repro.kernels.flash_attn import flash_attention
from repro.kernels.relax import relax_ell
from repro.kernels.segment_min import masked_min

rng = np.random.default_rng(42)


@pytest.mark.parametrize("n,deg", [(64, 128), (256, 256), (300, 130),
                                   (8, 640), (512, 512)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_relax_ell_sweep(n, deg, dtype):
    d_src = rng.uniform(0, 10, (n, deg)).astype(dtype)
    d_src[rng.random((n, deg)) < 0.1] = np.inf   # undiscovered sources
    w = rng.uniform(0.1, 1, (n, deg)).astype(dtype)
    mask = rng.random((n, deg)) < 0.7
    got = relax_ell(jnp.asarray(d_src), jnp.asarray(w), jnp.asarray(mask),
                    interpret=True)
    exp = ref.relax_ell_ref(jnp.asarray(d_src), jnp.asarray(w),
                            jnp.asarray(mask))
    assert np.array_equal(np.asarray(got), np.asarray(exp))  # min: exact


@pytest.mark.parametrize("n", [7, 128, 4096, 4097, 50000])
def test_masked_min_sweep(n):
    x = rng.uniform(-100, 100, n).astype(np.float32)
    m = rng.random(n) < 0.4
    got = masked_min(jnp.asarray(x), jnp.asarray(m), interpret=True)
    exp = ref.masked_min_ref(jnp.asarray(x), jnp.asarray(m))
    assert np.array_equal(np.asarray(got), np.asarray(exp))


def test_masked_min_empty_mask_is_inf():
    x = rng.uniform(0, 1, 100).astype(np.float32)
    assert np.isinf(np.asarray(
        masked_min(jnp.asarray(x), jnp.zeros(100, bool), interpret=True)))


@pytest.mark.parametrize("B,H,M,D,K", [
    (32, 16, 8, 10, 24),
    (64, 200, 39, 10, 200),   # the paper config (xDeepFM CIN layer 2)
    (32, 39, 39, 10, 200),    # CIN layer 1 (H_0 = n_fields)
    (32, 24, 8, 16, 12),
])
def test_cin_sweep(B, H, M, D, K):
    xk = rng.normal(size=(B, H, D)).astype(np.float32)
    x0 = rng.normal(size=(B, M, D)).astype(np.float32)
    w = rng.normal(size=(K, H, M)).astype(np.float32)
    got = cin_layer(jnp.asarray(xk), jnp.asarray(x0), jnp.asarray(w))
    exp = ref.cin_layer_ref(jnp.asarray(xk), jnp.asarray(x0),
                            jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("B,H,S,d", [(1, 2, 256, 64), (2, 4, 512, 128),
                                     (1, 1, 128, 32)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_flash_attention_sweep(B, H, S, d, causal, dtype):
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    q = jnp.asarray(rng.normal(size=(B, H, S, d)), dt)
    k = jnp.asarray(rng.normal(size=(B, H, S, d)), dt)
    v = jnp.asarray(rng.normal(size=(B, H, S, d)), dt)
    got = flash_attention(q, k, v, causal=causal)
    exp = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == "bfloat16" else 2e-3
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(exp, np.float32),
        rtol=tol, atol=tol)


def test_jnp_flash_matches_ref_long():
    """The pure-jnp production flash (models/attention.py) vs oracle."""
    from repro.models.attention import flash_attention_gqa
    B, S, Hkv, G, hd = 2, 384, 2, 3, 32
    q = jnp.asarray(rng.normal(size=(B, S, Hkv, G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    got = flash_attention_gqa(q, k, v, causal=True, block_k=128)
    # oracle: expand kv heads
    qq = q.reshape(B, S, Hkv * G, hd).transpose(0, 2, 1, 3)
    kk = jnp.repeat(k, G, axis=2).transpose(0, 2, 1, 3)
    vv = jnp.repeat(v, G, axis=2).transpose(0, 2, 1, 3)
    exp = ref.flash_attention_ref(qq, kk, vv, causal=True)
    exp = exp.transpose(0, 2, 1, 3).reshape(B, S, Hkv, G, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=2e-3, atol=2e-3)
