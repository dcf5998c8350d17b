"""Pallas TPU kernel: fused GradESTC projection  A = M^T G,  E = G - M A.

Why a kernel (DESIGN.md Sec. 3): this is the per-round compression hot spot.
Done naively it is two GEMMs with G (the large operand, l*m elements)
streamed from HBM twice -- the op is HBM-bandwidth-bound since k << l.  The
fusion streams each (l, bm) column block of G HBM->VMEM exactly once,
computes the (k, bm) coefficient block on the MXU, immediately forms the
residual block and writes both outputs.  HBM traffic drops from
  2*l*m (read) + l*m + k*m (write)   to   l*m (read) + l*m + k*m (write),
i.e. ~1.5x less for k << l -- directly attacking the roofline memory term.

Tiling
------
grid = (m // bm,).  Per grid step the VMEM working set is
    M (l, k)  +  G block (l, bm)  +  E block (l, bm)  +  A block (k, bm)
``ops.choose_block_m`` picks bm so this fits the v5e VMEM budget (~16 MB near
128-multiples for MXU alignment).  The basis M is small (k <= 128) and is
re-fetched per step from its BlockSpec (index_map pins it to block (0, 0), so
on TPU it stays VMEM-resident across the sweep).

Accumulation is f32 (``preferred_element_type``) regardless of input dtype.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref

__all__ = ["encode_pallas", "encode_quant_pallas"]


def _encode_kernel(m_ref, g_ref, a_ref, e_ref):
    """One (l, bm) column block: a = m^T g ; e = g - m a."""
    M = m_ref[...]                                  # (l, k)
    G = g_ref[...]                                  # (l, bm)
    A = jax.lax.dot_general(
        M, G, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                               # (k, bm) on the MXU
    Ghat = jax.lax.dot_general(
        M.astype(jnp.float32), A, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                               # (l, bm)
    a_ref[...] = A.astype(a_ref.dtype)
    e_ref[...] = (G.astype(jnp.float32) - Ghat).astype(e_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def encode_pallas(
    M: jnp.ndarray,
    G: jnp.ndarray,
    *,
    block_m: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused A = M^T G, E = G - M A.

    Args:
      M: (l, k) basis.  G: (l, m) reshaped gradient, m % block_m == 0.
      block_m: column tile width (multiple of 128 for MXU alignment).
      interpret: run the kernel body in Python on CPU (validation mode).

    Returns: (A (k, m), E (l, m)) in G.dtype.
    """
    l, k = M.shape
    l2, m = G.shape
    assert l == l2, f"M rows {l} != G rows {l2}"
    assert m % block_m == 0, f"m={m} not divisible by block_m={block_m}"

    grid = (m // block_m,)
    return pl.pallas_call(
        _encode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((l, k), lambda j: (0, 0)),          # M pinned
            pl.BlockSpec((l, block_m), lambda j: (0, j)),    # G column block
        ],
        out_specs=[
            pl.BlockSpec((k, block_m), lambda j: (0, j)),    # A
            pl.BlockSpec((l, block_m), lambda j: (0, j)),    # E
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, m), G.dtype),
            jax.ShapeDtypeStruct((l, m), G.dtype),
        ],
        interpret=interpret,
    )(M, G)


# ---------------------------------------------------------------------------
# fused projection + int8 coefficient wire (SVDFed steady-state uplink)
# ---------------------------------------------------------------------------

def _encode_quant_kernel(m_ref, g_ref, c_ref, s_ref, e_ref):
    """One (l, 512) column block: project, int8-quantize, residual vs ship."""
    M = m_ref[...]                                  # (l, k)
    G = g_ref[...]                                  # (l, 512)
    A = jax.lax.dot_general(
        M, G, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                               # (k, 512)
    scale = jnp.maximum(jnp.max(jnp.abs(A), axis=1, keepdims=True), 1e-12)
    codes = jnp.clip(jnp.round(A / scale * 127.0), -127.0, 127.0)
    ship = codes * (scale * ref.INV127)
    Ghat = jax.lax.dot_general(
        M.astype(jnp.float32), ship, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    c_ref[...] = codes.astype(jnp.int8)
    s_ref[...] = scale
    e_ref[...] = (G.astype(jnp.float32) - Ghat).astype(e_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def encode_quant_pallas(
    M: jnp.ndarray, G: jnp.ndarray, *, interpret: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused A = M^T G -> int8 wire -> E = G - M ship, one G pass.

    The column tile is pinned at 512 (the wire's scale-block width) so each
    grid step owns exactly one scale column; ``ops.encode_quant`` checks the
    VMEM budget fits this tile and refuses the shape otherwise.

    Args: M (l, k), G (l, m) with m % 512 == 0.
    Returns (codes int8 (k, m), scales f32 (m/512, k, 1), E (l, m) G.dtype) --
    the residual is against the *shipped* (dequantized) coefficients, the
    error the server actually cannot see.
    """
    l, k = M.shape
    l2, m = G.shape
    assert l == l2 and m % 512 == 0
    grid = (m // 512,)
    return pl.pallas_call(
        _encode_quant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((l, k), lambda j: (0, 0)),          # M pinned
            pl.BlockSpec((l, 512), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((k, 512), lambda j: (0, j)),
            pl.BlockSpec((None, k, 1), lambda j: (j, 0, 0)),
            pl.BlockSpec((l, 512), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, m), jnp.int8),
            jax.ShapeDtypeStruct((m // 512, k, 1), jnp.float32),
            jax.ShapeDtypeStruct((l, m), G.dtype),
        ],
        interpret=interpret,
    )(M, G)
