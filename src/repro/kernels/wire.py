"""Pallas TPU kernels: fused quantize -> bit-pack wire passes.

The codecs' wire formats (DESIGN.md "Wire-format layer") all reduce to the
same canonical uint32 packing: code ``i`` lands in word ``i // cpw`` at shift
``(i % cpw) * bits`` with ``cpw = 32 // bits``.  Done as separate XLA ops the
pipeline materializes a full-precision intermediate between quantize and
pack (and again between unpack and dequantize); each kernel here is one
HBM->VMEM->HBM pass per direction:

  * ``sign_pack_pallas``    -- bit = (g < 0) packed 32/word + per-row |g| sums
                               (signSGD; the dispatcher finishes the two-stage
                               mean so kernel == oracle bit-exactly)
  * ``sign_unpack_pallas``  -- words -> +-scale reconstruction
  * ``quant_pack_pallas``   -- block-quantize (per-512 scale, stochastic
                               rounding) and pack biased codes (FedPAQ/FedQClip)
  * ``unpack_dequant_pallas``-- words + scales -> f32 reconstruction
  * ``coeff_quant_pallas``  -- deterministic int8 wire for (k, m) coefficient
                               matrices, one scale per (row, 512-col block)
                               (GradESTC / SVDFed int8 coefficient wire)
  * ``coeff_dequant_pallas``-- int8 codes + scales -> f32 coefficients

Packing runs on the MXU.  Gathering every ``cpw``-th lane is a lane
compaction the TPU's vector unit has no instruction for (Mosaic refuses
strided lane slices), but it is a matrix product: the codes of one row
times a constant ``(512, nw)`` matrix holding ``2**shift`` at (code, word)
yields the words.  Codes and powers of two are exact in bf16, and each
product column is split into two 16-bit halves so every partial sum stays
an integer below 2**24 -- the f32 accumulator is exact, in any order.
Unpacking is the transpose: the four bytes of each word are spread over
its ``cpw`` lanes by a 0/1 matrix, the word is rebuilt per lane, and a
lane-dependent shift extracts the code.  Words are int32 inside the
kernels (Mosaic has no unsigned<->float casts); the dispatchers in
``ops.py`` bitcast to and from the uint32 wire.

Grids tile rows of a ``(rows, 512)`` layout, rows a multiple of 8 (the
dispatchers pad); per-row scales are ``(rows, 1)`` arrays whose blocks
span the full last dimension.  Coefficient scales are ``(nb, k, 1)``: one
``(k, 1)`` column per 512-column grid step.

All kernels are validated bit-exactly against the ``ref.py`` oracles in
interpret mode (tests/test_wire.py) and compiled for a TPU v5e in
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .quant import quant_levels

__all__ = [
    "sign_pack_pallas", "sign_unpack_pallas",
    "quant_pack_pallas", "unpack_dequant_pallas",
    "coeff_quant_pallas", "coeff_dequant_pallas",
]

WIRE_BLOCK = 512        # codes per scale row; keep in sync with ref.WIRE_BLOCK


@functools.lru_cache(maxsize=None)
def _pack_matrices(bits: int) -> Tuple[np.ndarray, np.ndarray]:
    """(lo, hi) ``(WIRE_BLOCK, nw)`` f32: code ``j*cpw + c`` enters word
    ``j`` with weight ``2**(c*bits)``, split at bit 16 into two halves."""
    cpw = 32 // bits
    nw = WIRE_BLOCK // cpw
    lo = np.zeros((WIRE_BLOCK, nw), np.float32)
    hi = np.zeros((WIRE_BLOCK, nw), np.float32)
    for c in range(cpw):
        shift = c * bits
        half = hi if shift >= 16 else lo
        half[np.arange(nw) * cpw + c, np.arange(nw)] = 2.0 ** (shift % 16)
    return lo, hi


@functools.lru_cache(maxsize=None)
def _expand_matrix(bits: int) -> np.ndarray:
    """``(nw, WIRE_BLOCK)`` 0/1 f32: word ``j`` feeds lanes ``j*cpw ..``."""
    cpw = 32 // bits
    return np.kron(np.eye(WIRE_BLOCK // cpw, dtype=np.float32),
                   np.ones((1, cpw), np.float32))


def _bf16(x: np.ndarray) -> jnp.ndarray:
    return jnp.asarray(x, jnp.bfloat16)


def _pinned(shape) -> pl.BlockSpec:
    """A whole small operand, resident in VMEM for every grid step."""
    return pl.BlockSpec(shape, lambda *_: (0,) * len(shape))


def _pack_rows(codes: jnp.ndarray, w_lo, w_hi) -> jnp.ndarray:
    """(br, 512) f32 holding integer codes in [0, 2**bits) ->
    (br, nw) int32 words (the uint32 wire's bit pattern)."""
    c = codes.astype(jnp.bfloat16)              # exact: codes < 2**8
    lo = jnp.dot(c, w_lo, preferred_element_type=jnp.float32)
    hi = jnp.dot(c, w_hi, preferred_element_type=jnp.float32)
    return lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 16)


def _unpack_rows(words: jnp.ndarray, expand, bits: int) -> jnp.ndarray:
    """(br, nw) int32 words -> (br, 512) int32 codes in [0, 2**bits)."""
    acc = None
    for b in range(4):
        byte = ((words >> (8 * b)) & 0xFF).astype(jnp.float32)
        rep = jnp.dot(byte.astype(jnp.bfloat16), expand,
                      preferred_element_type=jnp.float32).astype(jnp.int32)
        acc = rep if acc is None else acc | (rep << (8 * b))
    lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    shift = (lane & (32 // bits - 1)) * bits
    return jax.lax.shift_right_logical(acc, shift) & ((1 << bits) - 1)


def _row_pairwise_sum(x: jnp.ndarray) -> jnp.ndarray:
    """(br, 512) -> (br, 1): ``ref.pairwise_sum`` over lanes, built from
    lane rotations.  After the step with stride ``s`` lane ``i`` holds
    ``x[i] + x[i + s]`` of the previous level, so lane 0 walks exactly the
    oracle's tree (pairs (0,1), then (0-1, 2-3), ...)."""
    n = x.shape[-1]
    s = 1
    while s < n:
        x = x + pltpu.roll(x, n - s, 1)
        s *= 2
    return x[:, :1]


# ---------------------------------------------------------------------------
# sign wire
# ---------------------------------------------------------------------------

def _sign_pack_kernel(lo_ref, hi_ref, g_ref, w_ref, s_ref):
    g = g_ref[...].astype(jnp.float32)                  # (br, 512)
    w_ref[...] = _pack_rows((g < 0.0).astype(jnp.float32),
                            lo_ref[...], hi_ref[...])
    s_ref[...] = _row_pairwise_sum(jnp.abs(g))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def sign_pack_pallas(
    g2: jnp.ndarray, *, block_rows: int = 256, interpret: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """g2: (rows, 512) f32 -> (words (rows, 16) int32, rowsums (rows, 1)).

    The caller (ops.sign_wire) finishes the scale: sum(rowsums) / n -- the
    same two-stage reduction tree as ref.mean_abs_ref.
    """
    rows, block = g2.shape
    assert block == WIRE_BLOCK and rows % block_rows == 0
    lo, hi = _pack_matrices(1)
    nw = lo.shape[1]
    return pl.pallas_call(
        _sign_pack_kernel,
        grid=(rows // block_rows,),
        in_specs=[
            _pinned(lo.shape), _pinned(hi.shape),
            pl.BlockSpec((block_rows, block), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, nw), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, nw), jnp.int32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(_bf16(lo), _bf16(hi), g2)


def _sign_unpack_kernel(e_ref, w_ref, s_ref, o_ref):
    b = _unpack_rows(w_ref[...], e_ref[...], 1).astype(jnp.float32)
    o_ref[...] = ((1.0 - 2.0 * b) * s_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def sign_unpack_pallas(
    words2: jnp.ndarray, scale: jnp.ndarray, *, block_rows: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """words2: (rows, 16) int32, scale: () f32 -> (rows, 512) f32."""
    rows, nw = words2.shape
    assert nw == WIRE_BLOCK // 32 and rows % block_rows == 0
    ex = _expand_matrix(1)
    return pl.pallas_call(
        _sign_unpack_kernel,
        grid=(rows // block_rows,),
        in_specs=[
            _pinned(ex.shape),
            pl.BlockSpec((block_rows, nw), lambda i: (i, 0)),
            _pinned((1, 1)),                            # scale
        ],
        out_specs=pl.BlockSpec((block_rows, WIRE_BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, WIRE_BLOCK), jnp.float32),
        interpret=interpret,
    )(_bf16(ex), words2, scale.reshape(1, 1).astype(jnp.float32))


# ---------------------------------------------------------------------------
# block-quantize + pack wire (FedPAQ / FedQClip)
# ---------------------------------------------------------------------------

def _quant_pack_kernel(levels, lo_ref, hi_ref, g_ref, u_ref, w_ref, s_ref):
    g = g_ref[...].astype(jnp.float32)                  # (br, 512)
    u = u_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g), axis=1, keepdims=True), 1e-12)
    x = g / scale * levels
    lo = jnp.floor(x)
    codes = lo + (u < (x - lo)).astype(jnp.float32)
    codes = jnp.clip(codes, -levels, levels)
    # codes are exact small integers in f32; biased into [0, 2*levels]
    # they fit ``bits`` -- identical to the oracle's int path.
    w_ref[...] = _pack_rows(codes + levels, lo_ref[...], hi_ref[...])
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bits", "block_rows", "interpret"))
def quant_pack_pallas(
    g2: jnp.ndarray, u2: jnp.ndarray, *, bits: int = 8,
    block_rows: int = 256, interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(rows, 512) f32 -> (words (rows, 512*bits/32) int32, scales (rows, 1)).

    One fused pass of the FedPAQ uplink: per-row max-abs scale, stochastic
    rounding against u2, bias, bit-pack.  bits must divide 32 evenly into
    512 (i.e. bits in {2, 4, 8}; ops.py rejects other widths).
    """
    rows, block = g2.shape
    assert block == WIRE_BLOCK and rows % block_rows == 0
    assert bits in (2, 4, 8)
    lo, hi = _pack_matrices(bits)
    nw = lo.shape[1]
    return pl.pallas_call(
        functools.partial(_quant_pack_kernel, quant_levels(bits)),
        grid=(rows // block_rows,),
        in_specs=[
            _pinned(lo.shape), _pinned(hi.shape),
            pl.BlockSpec((block_rows, block), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, block), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, nw), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, nw), jnp.int32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(_bf16(lo), _bf16(hi), g2, u2)


def _unpack_dequant_kernel(levels, bits, e_ref, w_ref, s_ref, o_ref):
    codes = _unpack_rows(w_ref[...], e_ref[...], bits) - int(levels)
    # Reciprocal-multiply is the *defined* dequant (see ref.block_dequant_ref)
    inv = float(np.float32(1.0) / np.float32(levels))
    o_ref[...] = (codes.astype(jnp.float32)
                  * (s_ref[...] * inv)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block_rows", "interpret", "out_dtype"))
def unpack_dequant_pallas(
    words2: jnp.ndarray, scales: jnp.ndarray, *, bits: int = 8,
    block_rows: int = 256, interpret: bool = False, out_dtype=jnp.float32,
) -> jnp.ndarray:
    """(rows, 512*bits/32) int32 + (rows, 1) scales -> (rows, 512) out_dtype."""
    rows, nw = words2.shape
    assert rows % block_rows == 0 and nw == WIRE_BLOCK // (32 // bits)
    ex = _expand_matrix(bits)
    return pl.pallas_call(
        functools.partial(_unpack_dequant_kernel, quant_levels(bits), bits),
        grid=(rows // block_rows,),
        in_specs=[
            _pinned(ex.shape),
            pl.BlockSpec((block_rows, nw), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, WIRE_BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, WIRE_BLOCK), out_dtype),
        interpret=interpret,
    )(_bf16(ex), words2, scales)


# ---------------------------------------------------------------------------
# int8 coefficient wire (GradESTC / SVDFed)
# ---------------------------------------------------------------------------

def _scale_col(k: int) -> pl.BlockSpec:
    """Column block ``j``'s ``(k, 1)`` scales of an ``(nb, k, 1)`` array."""
    return pl.BlockSpec((None, k, 1), lambda j: (j, 0, 0))


def _coeff_quant_kernel(a_ref, c_ref, s_ref, p_ref):
    a = a_ref[...].astype(jnp.float32)                  # (k, 512)
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=1, keepdims=True), 1e-12)
    codes = jnp.clip(jnp.round(a / scale * 127.0), -127.0, 127.0)
    c_ref[...] = codes.astype(jnp.int8)
    s_ref[...] = scale
    p_ref[...] = codes * (scale * ref.INV127)           # shipped value


@functools.partial(jax.jit, static_argnames=("interpret",))
def coeff_quant_pallas(
    A: jnp.ndarray, *, interpret: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A: (k, m) f32, m % 512 == 0 -> (codes int8 (k, m), scales
    (m//512, k, 1), ship f32 (k, m)).  Deterministic round-to-nearest-even
    (see ref.coeff_quant_ref for why the wire must be deterministic here)."""
    k, m = A.shape
    assert m % WIRE_BLOCK == 0
    nb = m // WIRE_BLOCK
    return pl.pallas_call(
        _coeff_quant_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((k, WIRE_BLOCK), lambda j: (0, j))],
        out_specs=[
            pl.BlockSpec((k, WIRE_BLOCK), lambda j: (0, j)),
            _scale_col(k),
            pl.BlockSpec((k, WIRE_BLOCK), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, m), jnp.int8),
            jax.ShapeDtypeStruct((nb, k, 1), jnp.float32),
            jax.ShapeDtypeStruct((k, m), jnp.float32),
        ],
        interpret=interpret,
    )(A)


def _coeff_dequant_kernel(c_ref, s_ref, o_ref):
    c = c_ref[...].astype(jnp.float32)
    o_ref[...] = c * (s_ref[...] * ref.INV127)


@functools.partial(jax.jit, static_argnames=("interpret",))
def coeff_dequant_pallas(
    codes: jnp.ndarray, scales: jnp.ndarray, *, interpret: bool = False
) -> jnp.ndarray:
    """codes (k, m) int8 + scales (m//512, k, 1) -> (k, m) f32."""
    k, m = codes.shape
    assert m % WIRE_BLOCK == 0
    return pl.pallas_call(
        _coeff_dequant_kernel,
        grid=(m // WIRE_BLOCK,),
        in_specs=[
            pl.BlockSpec((k, WIRE_BLOCK), lambda j: (0, j)),
            _scale_col(k),
        ],
        out_specs=pl.BlockSpec((k, WIRE_BLOCK), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((k, m), jnp.float32),
        interpret=interpret,
    )(codes, scales)
