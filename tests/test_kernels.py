"""Per-kernel validation: shape/dtype sweeps, interpret=True vs pure-jnp
oracle (assert_allclose)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,hd,bq,bk", [
    (1, 64, 4, 4, 32, 32, 32),     # MHA
    (2, 128, 8, 2, 64, 64, 32),    # GQA 4x
    (1, 256, 4, 1, 64, 64, 64),    # MQA
    (2, 128, 6, 3, 128, 128, 64),  # non-pow2 heads
])
def test_flash_attention_sweep(dtype, b, s, h, kv, hd, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    out = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, window=window, block_q=32, block_k=32,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,hd,L,bk", [
    (1, 4, 4, 32, 128, 64),
    (3, 8, 2, 64, 512, 128),
    (2, 16, 8, 128, 256, 256),
])
def test_decode_attention_sweep(dtype, b, h, kv, hd, L, bk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, hd), dtype)
    kc = jax.random.normal(ks[1], (b, L, kv, hd), dtype)
    vc = jax.random.normal(ks[2], (b, L, kv, hd), dtype)
    nv = jnp.asarray(np.linspace(1, L, b).astype(np.int32))
    out = decode_attention(q, kc, vc, nv, block_k=bk, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, nv)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


def test_decode_attention_masks_tail_block():
    """n_valid inside the first block: later blocks fully skipped."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 32), jnp.float32)
    kc = jax.random.normal(ks[1], (1, 256, 4, 32), jnp.float32)
    vc = jax.random.normal(ks[2], (1, 256, 4, 32), jnp.float32)
    nv = jnp.array([3], jnp.int32)
    out = decode_attention(q, kc, vc, nv, block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, nv)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,nh,hd,ds,chunk", [
    (1, 64, 2, 32, 16, 16),
    (2, 128, 4, 64, 32, 32),
    (1, 256, 3, 32, 64, 64),
])
def test_ssd_scan_sweep(dtype, b, s, nh, hd, ds, chunk):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, s, nh, hd), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    B = jax.random.normal(ks[3], (b, s, ds), dtype)
    C = jax.random.normal(ks[4], (b, s, ds), dtype)
    y, st = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, str_ = ref.ssd_ref(x, dt, A, B, C, chunk=chunk)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(y.astype(jnp.float32),
                               yr.astype(jnp.float32), **tol)
    np.testing.assert_allclose(st, str_, atol=2e-3, rtol=2e-3)


def test_model_ssd_matches_oracle():
    """The model's chunked XLA path agrees with the sequential oracle."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    b, s, nh, hd, ds = 2, 96, 3, 16, 8
    x = jax.random.normal(ks[0], (b, s, nh, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    B = jax.random.normal(ks[3], (b, s, ds))
    C = jax.random.normal(ks[4], (b, s, ds))
    y, st = ssd_chunked(x, dt, A, B, C, 32)
    yr, str_ = ref.ssd_ref(x, dt, A, B, C, chunk=32)
    np.testing.assert_allclose(y, yr, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(st, str_, atol=2e-3, rtol=2e-3)


def test_ssd_scan_init_state_continuation():
    """Splitting a sequence across two scans with state carry == one scan."""
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    b, s, nh, hd, ds = 1, 128, 2, 16, 8
    x = jax.random.normal(ks[0], (b, s, nh, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    B = jax.random.normal(ks[3], (b, s, ds))
    C = jax.random.normal(ks[4], (b, s, ds))
    y_all, st_all = ref.ssd_ref(x, dt, A, B, C, chunk=32)
    h = s // 2
    y1, st1 = ref.ssd_ref(x[:, :h], dt[:, :h], A, B[:, :h], C[:, :h], 32)
    from repro.models.ssm import ssd_chunked
    y2, st2 = ssd_chunked(x[:, h:], dt[:, h:], A, B[:, h:], C[:, h:], 32,
                          init_state=st1)
    np.testing.assert_allclose(y2, y_all[:, h:], atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(st2, st_all, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("b,h,kv,hd,L,bk", [
    (2, 8, 2, 64, 256, 64),
    (1, 4, 4, 32, 128, 128),
])
def test_decode_attention_q8(b, h, kv, hd, L, bk):
    """int8-KV flash-decode kernel vs dequantized bf16 oracle."""
    from repro.kernels.decode_attention_q8 import decode_attention_q8
    from repro.models.cache import dequantize_kv, quantize_kv
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (b, L, kv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (b, L, kv, hd), jnp.float32)
    kq, ksc = quantize_kv(kc)
    vq, vsc = quantize_kv(vc)
    nv = jnp.asarray(np.linspace(L // 2, L, b).astype(np.int32))
    out = decode_attention_q8(q, kq, ksc, vq, vsc, nv, block_k=bk,
                              interpret=True)
    want = ref.decode_attention_ref(
        q, dequantize_kv(kq, ksc).astype(jnp.float32),
        dequantize_kv(vq, vsc).astype(jnp.float32), nv)
    np.testing.assert_allclose(out, want, atol=5e-3, rtol=5e-3)
    # and close to the unquantized attention
    exact = ref.decode_attention_ref(q, kc, vc, nv)
    assert float(jnp.max(jnp.abs(out - exact))) < 0.15


def test_model_forward_via_pallas_kernels():
    """forward_full routed through the Pallas flash-attention kernel
    (interpret mode) matches the XLA einsum path."""
    from repro.models import ModelConfig, forward_full, init_params
    cfg = ModelConfig(name="kd", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
                      dtype="float32", sliding_window=24)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 97)
    want, _, _ = forward_full(params, cfg, tokens=toks)
    got, _, _ = forward_full(params, cfg, tokens=toks,
                             attn_kernel="interpret")
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("b,s,h,kv,hd,window,bq,bk", [
    (1, 64, 4, 4, 32, 0, 32, 32),     # MHA
    (2, 64, 4, 2, 32, 0, 32, 32),     # GQA
    (1, 64, 4, 2, 32, 24, 32, 32),    # sliding window
    (1, 128, 6, 3, 64, 0, 64, 32),    # non-pow2 heads, rectangular blocks
])
def test_flash_attention_backward(b, s, h, kv, hd, window, bq, bk):
    """custom_vjp Pallas backward (dq/dk/dv) vs jax.grad of the oracle."""
    from repro.kernels.flash_attention_bwd import flash_attention_vjp
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32)
    ct = jax.random.normal(ks[3], (b, s, h, hd), jnp.float32)

    def f_pal(q, k, v):
        return jnp.sum(flash_attention_vjp(q, k, v, True, window, bq, bk,
                                           True) * ct)

    def f_ref(q, k, v):
        return jnp.sum(ref.flash_attention_ref(
            q, k, v, causal=True, window=window) * ct)

    o = flash_attention_vjp(q, k, v, True, window, bq, bk, True)
    np.testing.assert_allclose(o, ref.flash_attention_ref(
        q, k, v, causal=True, window=window), atol=3e-5, rtol=3e-5)
    g_pal = jax.grad(f_pal, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g_pal, g_ref):
        np.testing.assert_allclose(a, r, atol=3e-4, rtol=3e-4)
