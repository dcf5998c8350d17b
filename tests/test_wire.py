"""Wire-format properties: packed-word roundtrips and fused-kernel parity.

Two layers:

  * deterministic parametrized cases -- always run (container and CI) and
    pin the exact acceptance matrix: pack/unpack roundtrip over bit widths
    1-8 with odd tails, every fused wire kernel bit-exact against its
    ``ref.py`` oracle in interpret mode, and the ledger's wire-bit
    accounting identities;
  * a Hypothesis fuzz layer that widens the same checks over random sizes
    and seeds when hypothesis is installed (requirements-dev.txt / CI).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.ref as ref
from repro.kernels import ops
from repro.core.codecs import _coeff_wire_bits

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container image has no hypothesis; CI does
    HAVE_HYPOTHESIS = False

_SETTINGS = dict(max_examples=25, deadline=None)


def _rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# bit-pack / unpack roundtrip (the packing primitive is width-agnostic)
# ---------------------------------------------------------------------------

class TestPackRoundtrip:
    @pytest.mark.parametrize("bits", list(range(1, 9)))
    @pytest.mark.parametrize("n", [1, 5, 31, 32, 33, 512, 1000, 4097])
    def test_roundtrip(self, bits, n):
        codes = jnp.asarray(_rng(bits * 131 + n).integers(0, 2 ** bits, n),
                            jnp.uint32)
        words = ref.pack_codes_ref(codes, bits)
        assert words.dtype == jnp.uint32
        back = ref.unpack_codes_ref(words, bits, n)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))

    @pytest.mark.parametrize("bits", [1, 2, 4, 8])
    def test_word_count_is_exact(self, bits):
        # ceil(n * bits / 32) words -- the ledger's bit charge divided by 32,
        # rounded up; no slack word.
        for n in (1, 31, 32, 33, 511, 512, 513):
            codes = jnp.zeros((n,), jnp.uint32)
            cpw = 32 // bits
            assert ref.pack_codes_ref(codes, bits).shape == (-(-n // cpw),)

    def test_max_code_survives(self):
        # the largest biased quantizer code (2*levels = 2**bits - 2) and the
        # all-ones pattern both pack without overflow into neighbours
        for bits in (2, 4, 8):
            codes = jnp.full((97,), 2 ** bits - 1, jnp.uint32)
            back = ref.unpack_codes_ref(ref.pack_codes_ref(codes, bits),
                                        bits, 97)
            np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))


# ---------------------------------------------------------------------------
# sign wire (signSGD)
# ---------------------------------------------------------------------------

class TestSignWire:
    @pytest.mark.parametrize("n", [100, 512, 777, 5000, 65536])
    def test_kernel_matches_oracle(self, n):
        g = jnp.asarray(_rng(n).standard_normal(n), jnp.float32)
        wo, so = ops.sign_wire(g, use_kernel=False)
        wk, sk = ops.sign_wire(g, use_kernel=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(wo), np.asarray(wk))
        assert np.asarray(so) == np.asarray(sk)  # bit-exact scale
        ro = ops.sign_unwire(wo, so, n, use_kernel=False)
        rk = ops.sign_unwire(wk, sk, n, use_kernel=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(ro), np.asarray(rk))

    def test_wire_is_one_bit(self):
        n = 777
        g = jnp.asarray(_rng(1).standard_normal(n), jnp.float32)
        words, _ = ops.sign_wire(g, use_kernel=False)
        assert words.shape == (-(-n // 32),) and words.dtype == jnp.uint32

    def test_zero_ships_as_plus_scale(self):
        # 1-bit code book has no zero: bit = (g < 0), so g == 0 -> +scale
        g = jnp.asarray([0.0, -1.0, 2.0, 0.0], jnp.float32)
        w, s = ops.sign_wire(g, use_kernel=False)
        r = np.asarray(ops.sign_unwire(w, s, 4, use_kernel=False))
        sv = float(np.asarray(s))
        np.testing.assert_allclose(r, [sv, -sv, sv, sv], rtol=0)

    def test_parity_under_vmap(self):
        # codecs vmap encode over the client axis; the oracle's pinned
        # reduction (custom_vmap -> lax.map) must still match the kernel
        g = jnp.asarray(_rng(2).standard_normal((3, 1000)), jnp.float32)
        wo, so = jax.vmap(lambda x: ops.sign_wire(x, use_kernel=False))(g)
        wk, sk = jax.vmap(
            lambda x: ops.sign_wire(x, use_kernel=True, interpret=True))(g)
        np.testing.assert_array_equal(np.asarray(wo), np.asarray(wk))
        np.testing.assert_array_equal(np.asarray(so), np.asarray(sk))


# ---------------------------------------------------------------------------
# quantize+pack wire (FedPAQ / FedQClip block path)
# ---------------------------------------------------------------------------

class TestQuantWire:
    @pytest.mark.parametrize("bits", [2, 4, 8])
    @pytest.mark.parametrize("n", [512, 1000, 4096])
    def test_kernel_matches_oracle(self, bits, n):
        g = jnp.asarray(_rng(bits + n).standard_normal(n), jnp.float32)
        key = jax.random.PRNGKey(7)
        wo, so, po = ops.block_quant_wire(g, key, bits=bits, use_kernel=False)
        wk, sk, pk = ops.block_quant_wire(g, key, bits=bits, use_kernel=True,
                                          interpret=True)
        np.testing.assert_array_equal(np.asarray(wo), np.asarray(wk))
        np.testing.assert_array_equal(np.asarray(so), np.asarray(sk))
        do = ops.block_dequant_wire(wo, so, po, bits=bits, use_kernel=False)
        dk = ops.block_dequant_wire(wk, sk, pk, bits=bits, use_kernel=True,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(do), np.asarray(dk))
        assert np.isfinite(np.asarray(do)).all()

    @pytest.mark.parametrize("bits", [4, 8])
    def test_packing_is_lossless_on_codes(self, bits):
        # wire words carry the *same* integer codes block_quant_ref emits:
        # quantize -> pack -> unpack -> dequantize == quantize -> dequantize
        n = 1000
        g = jnp.asarray(_rng(9).standard_normal(n), jnp.float32)
        key = jax.random.PRNGKey(5)
        words, scales, pad = ops.block_quant_wire(g, key, bits=bits,
                                                  use_kernel=False)
        via_wire = ops.block_dequant_wire(words, scales, pad, bits=bits,
                                          use_kernel=False)
        gp = jnp.pad(g, (0, int(pad)))
        u = jax.random.uniform(key, gp.shape, jnp.float32)
        codes, scales0 = ref.block_quant_ref(gp, u, ref.WIRE_BLOCK, bits)
        direct = ref.block_dequant_ref(codes, scales0, ref.WIRE_BLOCK,
                                       bits)[:n]
        np.testing.assert_array_equal(np.asarray(via_wire), np.asarray(direct))

    @pytest.mark.parametrize("bits,block", [(3, 512), (8, 256)])
    def test_kernel_refuses_uncovered_widths(self, bits, block):
        # no kernel for this (bits, block): raising beats quietly running
        # the oracle inside a program that claims the kernel path
        g = jnp.zeros((1024,), jnp.float32)
        with pytest.raises(ValueError, match="no wire kernel"):
            ops.block_quant_wire(g, jax.random.PRNGKey(0), bits=bits,
                                 block=block, use_kernel=True)
        w, s, pad = ops.block_quant_wire(g, jax.random.PRNGKey(0), bits=bits,
                                         block=block, use_kernel=False)
        with pytest.raises(ValueError, match="no wire kernel"):
            ops.block_dequant_wire(w, s, pad, bits=bits, block=block,
                                   use_kernel=True)

    def test_one_bit_is_rejected(self):
        # 2^(bits-1)-1 = 0 levels at bits=1: that wire is ops.sign_wire
        g = jnp.zeros((512,), jnp.float32)
        with pytest.raises(AssertionError):
            ops.block_quant_wire(g, jax.random.PRNGKey(0), bits=1)


# ---------------------------------------------------------------------------
# coefficient wire (GradESTC / SVDFed): f32 / bf16 / int8
# ---------------------------------------------------------------------------

class TestCoeffWire:
    @pytest.mark.parametrize("k,m", [(4, 16), (8, 512), (6, 700)])
    def test_int8_kernel_matches_oracle(self, k, m):
        A = jnp.asarray(_rng(k * m).standard_normal((k, m)), jnp.float32)
        co, so, ho = ops.coeff_quant(A, use_kernel=False)
        ck, sk, hk = ops.coeff_quant(A, use_kernel=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(co), np.asarray(ck))
        np.testing.assert_array_equal(np.asarray(so), np.asarray(sk))
        np.testing.assert_array_equal(np.asarray(ho), np.asarray(hk))
        assert co.dtype == jnp.int8

    @pytest.mark.parametrize("wire_dtype", ["f32", "bf16", "int8"])
    def test_roundtrip_shapes_and_sanity(self, wire_dtype):
        A = jnp.asarray(_rng(3).standard_normal((6, 40)), jnp.float32)
        r = ops.coeff_roundtrip(A, wire_dtype, use_kernel=True,
                                interpret=True)
        assert r.shape == A.shape and r.dtype == A.dtype
        assert np.isfinite(np.asarray(r)).all()
        if wire_dtype == "f32":  # identity wire: bit-exact passthrough
            np.testing.assert_array_equal(np.asarray(r), np.asarray(A))
        elif wire_dtype == "bf16":
            np.testing.assert_array_equal(
                np.asarray(r), np.asarray(A.astype(jnp.bfloat16)
                                          .astype(jnp.float32)))

    def test_int8_codes_bounded_and_deterministic(self):
        A = jnp.asarray(_rng(11).standard_normal((5, 600)) * 30, jnp.float32)
        c1, s1, h1 = ops.coeff_quant(A, use_kernel=False)
        c2, s2, h2 = ops.coeff_quant(A, use_kernel=False)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        assert int(np.abs(np.asarray(c1)).max()) <= 127
        # ship == what the server reconstructs from (codes, scales)
        np.testing.assert_array_equal(
            np.asarray(h1), np.asarray(ref.coeff_dequant_ref(c1, s1)))

    def test_bf16_pack_words(self):
        a = jnp.asarray(_rng(13).standard_normal(41), jnp.float32)
        w = ref.bf16_pack_ref(a)
        assert w.dtype == jnp.uint32 and w.size * 2 >= a.size
        back = ref.bf16_unpack_ref(w, a.size)
        np.testing.assert_array_equal(
            np.asarray(back),
            np.asarray(a.astype(jnp.bfloat16).astype(jnp.float32)))


# ---------------------------------------------------------------------------
# fused project -> int8 wire -> residual (SVDFed steady state)
# ---------------------------------------------------------------------------

def _assert_gemm_equal(a, b, M, A, G=None):
    """``a`` and ``b`` both computed ``[G -] M @ A`` in f32, the kernel on
    padded column tiles and the oracle on the unpadded m.  Where m fills at
    least one 128-lane tile the two are bit-equal.  Narrower, XLA's CPU dot
    for the oracle's (l, m < 128) output sums the k terms in another order
    than the kernel's padded tile, so there the two are held to the
    rounding bound of a k-term dot product,
    2 * gamma_{k+1} * (|G| + |M| @ |A|), gamma_n = n*u / (1 - n*u),
    u = 2**-24 -- in practice one ulp."""
    if A.shape[1] >= 128:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return
    k = M.shape[1]
    u = 2.0 ** -24
    gamma = (k + 1) * u / (1 - (k + 1) * u)
    mag = np.abs(np.asarray(M, np.float64)) @ np.abs(np.asarray(A, np.float64))
    if G is not None:
        mag = mag + np.abs(np.asarray(G, np.float64))
    diff = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    assert (diff <= 2 * gamma * mag).all(), float((diff - 2 * gamma * mag).max())


class TestEncodeQuant:
    @pytest.mark.parametrize("l,k,m", [(128, 8, 512), (256, 16, 700),
                                       (64, 4, 100)])
    def test_kernel_matches_oracle(self, l, k, m):
        rng = _rng(l + m)
        M = jnp.asarray(np.linalg.qr(rng.standard_normal((l, k)))[0],
                        jnp.float32)
        G = jnp.asarray(rng.standard_normal((l, m)), jnp.float32)
        co, so, Eo = ops.encode_quant(M, G, use_kernel=False)
        ck, sk, Ek = ops.encode_quant(M, G, use_kernel=True, interpret=True)
        # the wire (codes, scales) is bit-exact
        np.testing.assert_array_equal(np.asarray(co), np.asarray(ck))
        np.testing.assert_array_equal(np.asarray(so), np.asarray(sk))
        # E = G - M @ ship (the client's residual) and decode = M @ ship
        # (the server's reconstruction) are GEMM outputs
        ship = ref.coeff_dequant_ref(co, so)
        _assert_gemm_equal(Eo, Ek, M, ship, G)
        go = ops.decode_wire(M, co, so, use_kernel=False)
        gk = ops.decode_wire(M, ck, sk, use_kernel=True, interpret=True)
        _assert_gemm_equal(go, gk, M, ship)

    def test_residual_consistent_with_decode(self):
        # E = G - M @ ship and decode(M, codes, scales) = M @ ship:
        # the client residual and the server reconstruction use the SAME
        # dequantized coefficients, so G ~= decode + E up to one GEMM
        rng = _rng(21)
        M = jnp.asarray(np.linalg.qr(rng.standard_normal((128, 8)))[0],
                        jnp.float32)
        G = jnp.asarray(rng.standard_normal((128, 512)), jnp.float32)
        codes, scales, E = ops.encode_quant(M, G, use_kernel=False)
        Ghat = ops.decode_wire(M, codes, scales, use_kernel=False)
        np.testing.assert_allclose(np.asarray(Ghat + E), np.asarray(G),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# ledger accounting identities
# ---------------------------------------------------------------------------

class TestWireBits:
    def test_f32_reproduces_history(self):
        # the default wire must charge exactly the historical 32*k*m bits
        for k, m in ((4, 16), (8, 512), (16, 700)):
            assert _coeff_wire_bits("f32", k, m) == 32 * k * m

    def test_bf16_halves(self):
        assert _coeff_wire_bits("bf16", 8, 512) == 16 * 8 * 512

    def test_int8_charges_codes_plus_scales(self):
        k, m = 8, 700
        nb = -(-m // ref.WIRE_BLOCK)
        assert _coeff_wire_bits("int8", k, m) == 8 * k * m + 32 * k * nb

    def test_ordering(self):
        k, m = 6, 1024
        assert (_coeff_wire_bits("int8", k, m)
                < _coeff_wire_bits("bf16", k, m)
                < _coeff_wire_bits("f32", k, m))


# ---------------------------------------------------------------------------
# Hypothesis fuzz layer (CI: requirements-dev.txt installs hypothesis)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    class TestFuzz:
        @given(bits=st.integers(1, 8), n=st.integers(1, 2048),
               seed=st.integers(0, 2 ** 16))
        @settings(**_SETTINGS)
        def test_pack_roundtrip(self, bits, n, seed):
            codes = jnp.asarray(_rng(seed).integers(0, 2 ** bits, n),
                                jnp.uint32)
            back = ref.unpack_codes_ref(ref.pack_codes_ref(codes, bits),
                                        bits, n)
            np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))

        @given(n=st.integers(1, 4096), seed=st.integers(0, 2 ** 16))
        @settings(**_SETTINGS)
        def test_sign_wire_parity(self, n, seed):
            g = jnp.asarray(_rng(seed).standard_normal(n), jnp.float32)
            wo, so = ops.sign_wire(g, use_kernel=False)
            wk, sk = ops.sign_wire(g, use_kernel=True, interpret=True)
            np.testing.assert_array_equal(np.asarray(wo), np.asarray(wk))
            assert np.asarray(so) == np.asarray(sk)

        @given(bits=st.sampled_from([2, 4, 8]), n=st.integers(1, 2048),
               seed=st.integers(0, 2 ** 16))
        @settings(**_SETTINGS)
        def test_quant_wire_parity(self, bits, n, seed):
            g = jnp.asarray(_rng(seed).standard_normal(n), jnp.float32)
            key = jax.random.PRNGKey(seed)
            wo, so, po = ops.block_quant_wire(g, key, bits=bits,
                                              use_kernel=False)
            wk, sk, pk = ops.block_quant_wire(g, key, bits=bits,
                                              use_kernel=True, interpret=True)
            np.testing.assert_array_equal(np.asarray(wo), np.asarray(wk))
            do = ops.block_dequant_wire(wo, so, po, bits=bits,
                                        use_kernel=False)
            dk = ops.block_dequant_wire(wk, sk, pk, bits=bits,
                                        use_kernel=True, interpret=True)
            np.testing.assert_array_equal(np.asarray(do), np.asarray(dk))

        @given(k=st.integers(1, 12), m=st.integers(1, 800),
               seed=st.integers(0, 2 ** 16))
        @settings(**_SETTINGS)
        def test_coeff_wire_parity(self, k, m, seed):
            A = jnp.asarray(_rng(seed).standard_normal((k, m)), jnp.float32)
            co, so, ho = ops.coeff_quant(A, use_kernel=False)
            ck, sk, hk = ops.coeff_quant(A, use_kernel=True, interpret=True)
            np.testing.assert_array_equal(np.asarray(co), np.asarray(ck))
            np.testing.assert_array_equal(np.asarray(ho), np.asarray(hk))
