"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode,
plus hypothesis properties for the activation codec."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.activation_codec import ops as codec_ops, ref as codec_ref
from repro.kernels.decode_attention import ops as da_ops, ref as da_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref


# ---------------------------------------------------------------- flash attn
@pytest.mark.parametrize("B,S,H,KV,D", [
    (1, 128, 2, 2, 32),      # MHA
    (2, 256, 4, 2, 64),      # GQA 2x
    (1, 384, 8, 2, 32),      # GQA 4x, non-pow2 seq blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, KV, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    ref = fa_ref.attention(q, k, v, causal=True)
    out = fa_ops.flash_attention(q, k, v, causal=True, impl="interpret",
                                 bq=128, bk=128)
    tol = 5e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 128, 2, 32))
    k = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    ref = fa_ref.attention(q, k, v, causal=False)
    out = fa_ops.flash_attention(q, k, v, causal=False, impl="interpret",
                                 bq=64, bk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)


# ------------------------------------------------------------- decode attn
@pytest.mark.parametrize("kv_len", [1, 7, 100, 256])
@pytest.mark.parametrize("B,H,KV,T,D", [(2, 4, 2, 256, 32), (1, 8, 8, 512, 64)])
def test_decode_attention_sweep(B, H, KV, T, D, kv_len):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, KV, T, D))
    v = jax.random.normal(ks[2], (B, KV, T, D))
    ref = da_ref.decode_attention(q, k, v, kv_len)
    out = da_ops.decode_attention(q, k, v, jnp.int32(kv_len),
                                  impl="interpret", bk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_decode_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 4, 2, 32), jnp.bfloat16)[:, 0]
    k = jax.random.normal(ks[1], (2, 2, 256, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 2, 256, 32), jnp.bfloat16)
    ref = da_ref.decode_attention(q, k, v, 200)
    out = da_ops.decode_attention(q, k, v, jnp.int32(200), impl="interpret",
                                  bk=128)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)


# ---------------------------------------------------------------- ssd scan
@pytest.mark.parametrize("B,T,H,P,N,chunk", [
    (2, 128, 3, 16, 32, 32),
    (1, 256, 2, 32, 16, 64),
    (1, 64, 1, 8, 8, 64),       # T == chunk
])
def test_ssd_scan_sweep(B, T, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, T, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, T, N)) * 0.3
    y_ref, s_ref = ssd_ref.ssd(x, dt, A, Bm, Cm, chunk)
    y, s = ssd_ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, impl="interpret")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=2e-5)


def test_ssd_state_equals_sequential():
    """Chunked kernel state must match a literal per-token recurrence."""
    from repro.models.ssm import ssd_step
    B, T, H, P, N = 1, 48, 2, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (B, T, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, T, N)) * 0.3
    y_k, s_k = ssd_ops.ssd_scan(x, dt, A, Bm, Cm, chunk=16, impl="interpret")
    S = jnp.zeros((B, H, N, P))
    ys = []
    for t in range(T):
        y, S = ssd_step(S, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        ys.append(y)
    y_seq = jnp.stack(ys, 1)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(S), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_seq), atol=1e-4)


# ------------------------------------------------------------------- codec
@pytest.mark.parametrize("shape", [(4, 128), (256, 384), (2, 17, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_codec_roundtrip_sweep(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(6), shape, dtype)
    q, s = codec_ops.quantize(x)
    back = codec_ops.dequantize(q, s, dtype)
    amax = float(jnp.max(jnp.abs(x.astype(jnp.float32))))
    err = float(jnp.max(jnp.abs(back.astype(jnp.float32)
                                - x.astype(jnp.float32))))
    assert err <= amax / 127.0 + 1e-2 * amax


def test_codec_pallas_matches_ref():
    x = jax.random.normal(jax.random.PRNGKey(7), (256, 384), jnp.bfloat16)
    qi, si = codec_ops.quantize(x, impl="interpret")
    qr, sr = codec_ref.quantize_int8(x)
    assert bool(jnp.all(qi == qr))
    np.testing.assert_allclose(np.asarray(si), np.asarray(sr))
    di = codec_ops.dequantize(qi, si, impl="interpret")
    dr = codec_ref.dequantize_int8(qr, sr)
    assert bool(jnp.all(di == dr))


@given(st.integers(1, 8), st.integers(1, 4), st.floats(0.01, 100.0))
@settings(max_examples=20, deadline=None)
def test_codec_error_bound_property(rows, blocks, scale):
    x = (jax.random.normal(jax.random.PRNGKey(rows * 7 + blocks),
                           (rows, blocks * 128)) * scale).astype(jnp.float32)
    q, s = codec_ref.quantize_int8(x)
    back = codec_ref.dequantize_int8(q, s, jnp.float32)
    xb = np.asarray(x).reshape(rows, blocks, 128)
    bb = np.asarray(back).reshape(rows, blocks, 128)
    amax = np.abs(xb).max(-1, keepdims=True)
    assert np.all(np.abs(bb - xb) <= amax / 127.0 * 1.01 + 1e-7)


def test_codec_wire_bytes():
    assert codec_ref.wire_bytes((1, 17, 3072)) == 17 * 3072 + 17 * 24 * 4


@pytest.mark.parametrize("rows", [1, 273, 600])
def test_codec_kernels_any_row_count(rows):
    """Row counts that no row tile divides (an OpenVLA cut is 273 rows)
    are padded inside the kernels: int8 and int4, both directions, equal
    the oracle bit for bit."""
    x = jax.random.normal(jax.random.PRNGKey(rows), (1, rows, 512),
                          jnp.bfloat16)
    qi, si = codec_ops.quantize(x, impl="interpret")
    qr, sr = codec_ref.quantize_int8(x)
    assert qi.shape == qr.shape and si.shape == sr.shape
    assert bool(jnp.all(qi == qr)) and bool(jnp.all(si == sr))
    assert bool(jnp.all(codec_ops.dequantize(qr, sr, impl="interpret")
                        == codec_ref.dequantize_int8(qr, sr)))
    pi, s4i = codec_ops.quantize_int4(x, impl="interpret")
    pr, s4r = codec_ref.quantize_int4(x)
    assert bool(jnp.all(pi == pr)) and bool(jnp.all(s4i == s4r))
    assert bool(jnp.all(codec_ops.dequantize_int4(pr, s4r, impl="interpret")
                        == codec_ref.dequantize_int4(pr, s4r)))


@pytest.mark.parametrize("impl", ["pallas", "interpret"])
def test_codec_kernel_refuses_other_block(impl):
    """A block size the kernels do not implement is an error, never a
    silent switch to the jnp reference."""
    x = jnp.ones((8, 512), jnp.float32)
    q, s = codec_ref.quantize_int8(x, 64)
    p4, s4 = codec_ref.quantize_int4(x, 64)
    calls = [lambda: codec_ops.quantize(x, impl=impl, block=64),
             lambda: codec_ops.dequantize(q, s, impl=impl, block=64),
             lambda: codec_ops.quantize_int4(x, impl=impl, block=64),
             lambda: codec_ops.dequantize_int4(p4, s4, impl=impl, block=64)]
    for call in calls:
        with pytest.raises(ValueError, match="block=64"):
            call()
    # the jnp path implements every block size
    q2, s2 = codec_ops.quantize(x, impl="jnp", block=64)
    assert bool(jnp.all(q2 == q))


@pytest.mark.parametrize("sizes,stacked", [((32, 0, 16, 48), False),
                                           ((0, 0, 0, 0), False),
                                           ((16, 64, 0, 32), True)])
def test_grouped_matmul_kernel_matches_ragged_dot(sizes, stacked):
    """The Pallas grouped matmul (interpreted) gives each group's rows the
    product with that group's weights, as XLA's ragged dot does, with
    empty groups skipped and, for a stack of layers, the traced layer
    read in place."""
    from repro.kernels.grouped_matmul import ops as gmm_ops
    G, K, N, tm, M = 4, 64, 256, 16, 160
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (M, K)).astype(jnp.bfloat16)
    w = jax.random.normal(kw, ((3,) if stacked else ()) + (G, K, N))
    w = w.astype(jnp.bfloat16)
    layer = jnp.int32(1) if stacked else None
    g = jnp.array(sizes, jnp.int32)
    got = gmm_ops.grouped_matmul(x, w, g, layer, tm=tm, impl="interpret")
    want = gmm_ops.grouped_matmul(x, w, g, layer, tm=tm, impl="xla")
    rows = sum(sizes)
    # bf16 outputs of sums of 64 unit products (~8): one rounding is 0.03
    np.testing.assert_allclose(np.asarray(got[:rows], np.float32),
                               np.asarray(want[:rows], np.float32),
                               rtol=1e-2, atol=1e-2)
    wl = w[1] if stacked else w
    start = 0
    for e, n in enumerate(sizes):
        exact = np.asarray(x[start:start + n], np.float32) \
            @ np.asarray(wl[e], np.float32)
        np.testing.assert_allclose(np.asarray(got[start:start + n],
                                              np.float32), exact,
                                   rtol=2e-2, atol=5e-2)
        start += n
