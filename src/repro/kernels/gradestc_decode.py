"""Pallas TPU kernel: GradESTC reconstruction  Ghat = M A.

The server-side decompression (Alg. 2 line 2).  A thin blocked GEMM -- kept as
a kernel so that decode shares the same VMEM tiling discipline as encode and
so the benchmark harness can time both sides of the codec.

grid = (l // bl, m // bm); per step the MXU contracts the full k dimension
(k <= 128 always fits).  f32 accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref

__all__ = ["decode_pallas", "decode_wire_pallas"]


def _decode_kernel(m_ref, a_ref, o_ref):
    out = jax.lax.dot_general(
        m_ref[...], a_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_l", "block_m", "interpret"))
def decode_pallas(
    M: jnp.ndarray,
    A: jnp.ndarray,
    *,
    block_l: int = 256,
    block_m: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ghat = M @ A.  M: (l, k), A: (k, m); l % block_l == m % block_m == 0."""
    l, k = M.shape
    k2, m = A.shape
    assert k == k2
    assert l % block_l == 0 and m % block_m == 0

    grid = (l // block_l, m // block_m)
    return pl.pallas_call(
        _decode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_l, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, block_m), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_l, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((l, m), M.dtype),
        interpret=interpret,
    )(M, A)


# ---------------------------------------------------------------------------
# fused int8-dequant + reconstruction (server side of the int8 coeff wire)
# ---------------------------------------------------------------------------

def _decode_wire_kernel(m_ref, c_ref, s_ref, o_ref):
    A = c_ref[...].astype(jnp.float32) * (s_ref[...] * ref.INV127)  # (k, 512)
    out = jax.lax.dot_general(
        m_ref[...], A, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_l", "interpret"))
def decode_wire_pallas(
    M: jnp.ndarray,
    codes: jnp.ndarray,
    scales: jnp.ndarray,
    *,
    block_l: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ghat = M (codes * scales / 127): dequantize the int8 coefficient wire
    inside the GEMM pass instead of materializing the f32 coefficients.

    M: (l, k), codes: (k, m) int8, scales: (m/512, k, 1);
    l % block_l == 0 and m % 512 == 0 (the wire's scale-block width).
    """
    l, k = M.shape
    k2, m = codes.shape
    assert k == k2 and l % block_l == 0 and m % 512 == 0

    grid = (l // block_l, m // 512)
    return pl.pallas_call(
        _decode_wire_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_l, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, 512), lambda i, j: (0, j)),
            pl.BlockSpec((None, k, 1), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_l, 512), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((l, m), M.dtype),
        interpret=interpret,
    )(M, codes, scales)
