"""Pallas kernel validation: shape/dtype sweep vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.gradestc_decode import decode_pallas
from repro.kernels.gradestc_encode import encode_pallas
from repro.kernels.quant import block_dequant_pallas, block_quant_pallas

ENCODE_SHAPES = [
    # (l, k, m, block_m)
    (128, 8, 128, 128),
    (256, 16, 384, 128),
    (512, 32, 256, 256),
    (384, 4, 512, 128),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _orthonormal(key, l, k, dt):
    M, _ = jnp.linalg.qr(jax.random.normal(key, (l, k), jnp.float32))
    return M.astype(dt)


@pytest.mark.parametrize("l,k,m,bm", ENCODE_SHAPES)
@pytest.mark.parametrize("dt", DTYPES)
class TestEncodeKernel:
    def test_matches_oracle(self, l, k, m, bm, dt, key):
        M = _orthonormal(key, l, k, dt)
        G = jax.random.normal(jax.random.PRNGKey(1), (l, m), dt)
        A1, E1 = encode_pallas(M, G, block_m=bm, interpret=True)
        A0, E0 = ref.encode_ref(M, G)
        tol = 2e-2 if dt == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(A1, np.float32),
                                   np.asarray(A0, np.float32), rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(E1, np.float32),
                                   np.asarray(E0, np.float32), rtol=tol, atol=tol)

    def test_residual_orthogonal_to_basis(self, l, k, m, bm, dt, key):
        """The kernel must preserve M^T E = 0 (Formula 7)."""
        M = _orthonormal(key, l, k, dt)
        G = jax.random.normal(jax.random.PRNGKey(2), (l, m), dt)
        _, E = encode_pallas(M, G, block_m=bm, interpret=True)
        cross = np.asarray(
            M.astype(jnp.float32).T @ E.astype(jnp.float32)
        )
        scale = float(jnp.abs(G.astype(jnp.float32)).max())
        tol = 5e-2 if dt == jnp.bfloat16 else 1e-3
        assert np.abs(cross).max() < tol * scale * np.sqrt(l)


@pytest.mark.parametrize("l,k,m,bm", ENCODE_SHAPES)
@pytest.mark.parametrize("dt", DTYPES)
def test_decode_kernel(l, k, m, bm, dt, key):
    M = _orthonormal(key, l, k, dt)
    A = jax.random.normal(jax.random.PRNGKey(3), (k, m), dt)
    bl = 128 if l % 128 == 0 else l
    out = decode_pallas(M, A, block_l=bl, block_m=128, interpret=True)
    exp = ref.decode_ref(M, A)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("n,block,br", [(4096, 512, 4), (2048, 256, 8), (8192, 512, 16)])
@pytest.mark.parametrize("bits", [4, 8])
def test_quant_kernel_bit_exact(n, block, br, bits, key):
    g = jax.random.normal(key, (n,), jnp.float32) * 3.0
    u = jax.random.uniform(jax.random.PRNGKey(5), (n,))
    c1, s1 = block_quant_pallas(g, u, block=block, bits=bits, block_rows=br,
                                interpret=True)
    c0, s0 = ref.block_quant_ref(g, u, block, bits)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-6)
    d1 = block_dequant_pallas(c1, s1, block=block, bits=bits, block_rows=br,
                              interpret=True)
    d0 = ref.block_dequant_ref(c0, s0, block, bits)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d0), rtol=1e-6)


class TestOpsWrappers:
    def test_encode_pads_ragged_m(self, key):
        M = _orthonormal(key, 300, 12, jnp.float32)
        G = jax.random.normal(key, (300, 777))
        A, E = ops.encode(M, G)
        A0, E0 = ref.encode_ref(M, G)
        np.testing.assert_allclose(np.asarray(A), np.asarray(A0), atol=1e-4)
        np.testing.assert_allclose(np.asarray(E), np.asarray(E0), atol=1e-4)

    def test_decode_roundtrip(self, key):
        M = _orthonormal(key, 256, 8, jnp.float32)
        G = jax.random.normal(key, (256, 200))
        A, _ = ops.encode(M, G)
        Ghat = ops.decode(M, A)
        np.testing.assert_allclose(
            np.asarray(Ghat), np.asarray(ref.decode_ref(M, A)), atol=1e-4
        )

    def test_quant_roundtrip_with_padding(self, key):
        g = jax.random.normal(key, (1000,))
        codes, scales, pad = ops.block_quantize(g, key)
        gd = ops.block_dequantize(codes, scales, pad)
        assert gd.shape == g.shape
        step = 2.0 * float(scales.max()) / 127
        assert float(jnp.abs(gd - g).max()) <= step + 1e-5

    def test_choose_block_m_fits_budget(self):
        for l in (512, 4096, 14336, 29568):
            for k in (16, 64, 128):
                for dt in (jnp.float32, jnp.bfloat16):
                    bm = ops.choose_block_m(l, k, dt)
                    s = jnp.dtype(dt).itemsize
                    if bm == 0:
                        # infeasible for single-pass: even bm=128 over budget
                        assert l * k * s + (2 * l + k) * 128 * s > ops.VMEM_BUDGET_BYTES
                    else:
                        assert bm % 128 == 0
                        assert (l * k * s + (2 * l + k) * bm * s
                                <= ops.VMEM_BUDGET_BYTES * 1.25)

    def test_encode_falls_back_for_huge_l(self, key):
        """l too large for VMEM: the kernel refuses the shape instead of
        quietly running the oracle, and the XLA path (use_kernel=False) is
        the caller's explicit fallback; a scaled-down l runs the kernel."""
        assert ops.choose_block_m(29568, 64, jnp.float32) == 0
        huge_M = jax.ShapeDtypeStruct((29568, 64), jnp.float32)
        huge_G = jax.ShapeDtypeStruct((29568, 256), jnp.float32)
        with pytest.raises(ValueError, match="VMEM"):
            jax.eval_shape(ops.encode, huge_M, huge_G)
        jax.eval_shape(functools.partial(ops.encode, use_kernel=False),
                       huge_M, huge_G)
        M = _orthonormal(key, 29568 // 16, 8, jnp.float32)  # scaled-down check
        G = jax.random.normal(key, (M.shape[0], 64))
        A, E = ops.encode(M, G)
        A0, E0 = ref.encode_ref(M, G)
        np.testing.assert_allclose(np.asarray(A), np.asarray(A0), atol=1e-4)


class TestDecodeWiring:
    """The decode kernel is wired into the GradESTC reconstruction and
    downlink decode paths (``core.gradestc.reconstruct`` / ``decompress``)
    under the same use_pallas flag as encode."""

    def test_reconstruct_routes_through_decode_kernel(self, key):
        from repro.core import gradestc as ge
        M = _orthonormal(key, 96, 8, jnp.float32)
        A = jax.random.normal(jax.random.PRNGKey(7), (8, 100), jnp.float32)
        out = ge.reconstruct(M, A, use_pallas=True, pallas_interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(M @ A),
                                   rtol=1e-4, atol=1e-4)

    def test_decompress_pallas_matches_plain(self, key):
        from repro.core import gradestc as ge
        l, k, d, m = 64, 4, 2, 37
        M = _orthonormal(key, l, k, jnp.float32)
        payload = ge.Payload(
            replaced_mask=jnp.array([True, False, True, False]),
            new_vectors=jax.random.normal(jax.random.PRNGKey(8), (d, l)),
            coeffs=jax.random.normal(jax.random.PRNGKey(9), (k, m)),
            d_r=jnp.asarray(d, jnp.int32),
            init=jnp.zeros((), jnp.bool_),
        )
        st = ge.DecompressorState(M=M)
        st0, g0 = ge.decompress(st, payload)
        st1, g1 = ge.decompress(st, payload, use_pallas=True,
                                pallas_interpret=True)
        np.testing.assert_array_equal(np.asarray(st0.M), np.asarray(st1.M))
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                                   rtol=1e-4, atol=1e-4)


class TestFlashAttention:
    """Fused flash attention kernel (SPerf, qwen2 prefill) vs the reference
    attention path."""

    @pytest.mark.parametrize("B,Sq,H,KV,hd,causal,window", [
        (2, 128, 4, 2, 32, True, 0),
        (1, 256, 8, 8, 16, True, 64),
        (2, 128, 4, 1, 64, False, 0),
        (1, 192, 6, 3, 32, True, 0),
    ])
    def test_matches_reference(self, B, Sq, H, KV, hd, causal, window, key):
        from repro.kernels.flash_attention import flash_attention_pallas
        from repro.models.layers import attention, repeat_kv
        q = jax.random.normal(key, (B, Sq, H, hd), jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(1), (B, Sq, KV, hd), jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(2), (B, Sq, KV, hd), jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                     block_q=64, block_kv=64, interpret=True)
        exp = attention(q, repeat_kv(k, H // KV), repeat_kv(v, H // KV),
                        causal=causal, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-4, atol=1e-4)

    def test_bf16(self, key):
        from repro.kernels.flash_attention import flash_attention_pallas
        from repro.models.layers import attention, repeat_kv
        q = jax.random.normal(key, (1, 128, 4, 32), jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 2, 32), jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 2, 32), jnp.bfloat16)
        out = flash_attention_pallas(q, k, v, block_q=64, block_kv=64,
                                     interpret=True)
        exp = attention(q, repeat_kv(k, 2), repeat_kv(v, 2))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=5e-2, atol=5e-2)
