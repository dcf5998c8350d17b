"""Public jit'd wrappers around the Pallas kernels.

Handles: block-shape selection against a VMEM budget, padding to tile
multiples, and backend dispatch -- on TPU the kernels run compiled,
elsewhere in interpret mode.  ``use_kernel=False`` selects the pure-jnp
``ref.py`` oracle; with ``use_kernel=True`` a shape or bit width no kernel
covers raises instead of quietly running the oracle, so a kernel can never
drop out of a device program unseen.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import ref
from .gradestc_decode import decode_pallas, decode_wire_pallas
from .gradestc_encode import encode_pallas, encode_quant_pallas
from .quant import block_dequant_pallas, block_quant_pallas
from .wire import (
    coeff_quant_pallas, quant_pack_pallas, sign_pack_pallas,
    sign_unpack_pallas, unpack_dequant_pallas,
)

__all__ = [
    "encode", "decode", "block_quantize", "block_dequantize",
    "quantize_update", "choose_block_m", "VMEM_BUDGET_BYTES",
    "sign_wire", "sign_unwire", "block_quant_wire", "block_dequant_wire",
    "coeff_quant", "coeff_roundtrip", "encode_quant", "decode_wire",
]

# v5e VMEM is ~128 MiB/core architecturally but ~16 MiB is the practical
# working budget per pallas_call after double buffering; stay under that.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def choose_block_m(l: int, k: int, dtype=jnp.float32, budget: int = VMEM_BUDGET_BYTES) -> int:
    """Largest 128-multiple bm such that M + G-block + E-block + A-block fit.

    VMEM model (bytes): l*k*s  +  2*l*bm*s  +  k*bm*s,  s = dtype size.
    Returns 0 when even bm=128 cannot fit (l too large for the single-pass
    kernel; ops.encode then refuses the shape -- ``use_kernel=False`` runs
    the XLA path, which tiles l internally at the cost of reading G
    twice)."""
    s = jnp.dtype(dtype).itemsize
    fixed = l * k * s
    per_col = (2 * l + k) * s
    bm = (budget - fixed) // per_col
    bm = (bm // 128) * 128
    if bm < 128:
        return 0
    return int(min(bm, 1024))


def _pad_cols(G: jnp.ndarray, mult: int) -> Tuple[jnp.ndarray, int]:
    from repro.core.reshaping import pad_to_block

    Gp, m = pad_to_block(G, mult, axis=-1)
    return Gp, Gp.shape[-1] - m


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def encode(
    M: jnp.ndarray, G: jnp.ndarray, *, use_kernel: bool = True, interpret: bool | None = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused A = M^T G, E = G - M A (see gradestc_encode.py)."""
    if not use_kernel:
        return ref.encode_ref(M, G)
    interp = (not _on_tpu()) if interpret is None else interpret
    l, k = M.shape
    bm = choose_block_m(l, k, G.dtype)
    if bm == 0:
        raise ValueError(
            f"encode kernel: basis ({l}, {k}) leaves no 128-column tile "
            f"inside the {VMEM_BUDGET_BYTES}-byte VMEM budget; "
            "use_kernel=False runs the XLA path")
    # Never tile wider than the matrix itself: a small-m G only pays for
    # padding up to the next 128 multiple, not up to the VMEM-budget block.
    m128 = G.shape[1] + ((-G.shape[1]) % 128)
    bm = min(bm, m128)
    Gp, pad = _pad_cols(G, bm)
    A, E = encode_pallas(M, Gp, block_m=bm, interpret=interp)
    if pad:
        A, E = A[:, : G.shape[1]], E[:, : G.shape[1]]
    return A, E


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def decode(
    M: jnp.ndarray, A: jnp.ndarray, *, use_kernel: bool = True, interpret: bool | None = None
) -> jnp.ndarray:
    """Ghat = M @ A (see gradestc_decode.py)."""
    if not use_kernel:
        return ref.decode_ref(M, A)
    interp = (not _on_tpu()) if interpret is None else interpret
    l, k = M.shape
    m = A.shape[1]
    bl = 256 if l % 256 == 0 else (128 if l % 128 == 0 else l)
    # Never tile wider than the coefficient matrix itself: a small-m A only
    # pays for padding to the next 128 multiple (same rule as encode).
    bm = min(256, m + ((-m) % 128))
    Ap, pad = _pad_cols(A, bm)
    out = decode_pallas(M, Ap, block_l=bl, block_m=bm, interpret=interp)
    return out[:, :m] if pad else out


#: row-tile cap of the row-tiled kernels: 256 x 512 f32 = 512 KiB per
#: operand block, double-buffered well inside the VMEM budget
_MAX_TILE_ROWS = 256


def _row_tiling(rows: int) -> Tuple[int, int]:
    """(padded rows, row tile) of a row-tiled kernel operand: the tile is
    ``rows`` rounded up to the TPU's 8-row sublane tile, capped at
    ``_MAX_TILE_ROWS``, and the rows are zero-padded to a multiple of it."""
    tile = min(-(-rows // 8) * 8, _MAX_TILE_ROWS)
    return -(-rows // tile) * tile, tile


def _pad_rows(x2: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    """Zero-pad a (rows, ...) operand to ``_row_tiling``; returns the padded
    array and its row tile."""
    rows = x2.shape[0]
    rows_p, tile = _row_tiling(rows)
    if rows_p != rows:
        x2 = jnp.pad(x2, [(0, rows_p - rows)] + [(0, 0)] * (x2.ndim - 1))
    return x2, tile


def block_quantize(
    g: jnp.ndarray, key: jax.Array, *, block: int = 512, bits: int = 8,
    use_kernel: bool = True, interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Flat stochastic int8 quantization.  Returns (codes, scales, pad)."""
    n = g.shape[0]
    pad = (-n) % block
    gp = jnp.pad(g, (0, pad)) if pad else g
    u = jax.random.uniform(key, gp.shape, jnp.float32)
    if not use_kernel:
        codes, scales = ref.block_quant_ref(gp, u, block, bits)
        return codes, scales, pad
    interp = (not _on_tpu()) if interpret is None else interpret
    rows = gp.shape[0] // block
    rows_p, br = _row_tiling(rows)
    extra = (rows_p - rows) * block
    codes, scales = block_quant_pallas(
        jnp.pad(gp, (0, extra)), jnp.pad(u, (0, extra)), block=block,
        bits=bits, block_rows=br, interpret=interp
    )
    return codes[: rows * block], scales[:rows], pad


def quantize_update(
    g: jnp.ndarray, key: jax.Array, *, bits: int = 8, block: int = 512,
    use_pallas: bool = False, interpret: bool | None = None,
) -> jnp.ndarray:
    """Quantize-dequantize a flat update for the FL quantization codecs
    (FedPAQ, FedQClip) -- the same ``use_pallas`` switch the GradESTC
    encode takes.

    Both paths materialize the **packed uint32 wire words** on device and
    reconstruct from them, so what the codec charges the ledger for is what
    actually exists in memory.  The pack/unpack roundtrip is lossless on the
    integer codes, so reconstructions are bit-identical to the pre-wire
    formulation.

    ``use_pallas=False``: the paper's global-max-abs stochastic quantizer
    (one 32-bit scale per tensor; ``core.baselines.quantize_stochastic``),
    packed via the jnp oracle.
    ``use_pallas=True``: the TPU-native block-local quantizer fused with the
    bit-pack (``wire.quant_pack_pallas``; one 32-bit scale per ``block``
    entries, interpret mode on CPU).  Returns the server-side
    reconstruction; byte accounting for either wire format lives with the
    codec (``core.codecs.FedPAQCodec.charge_bits``).
    """
    if not use_pallas:
        from repro.core.baselines import dequantize, quantize_stochastic

        codes, scale = quantize_stochastic(g, key, bits)
        words = ref.pack_codes_ref(codes, bits)
        codes2 = ref.unpack_codes_ref(words, bits, g.shape[0]).astype(jnp.int32)
        return dequantize(codes2, scale, bits).astype(g.dtype)
    words, scales, pad = block_quant_wire(
        g, key, block=block, bits=bits, interpret=interpret
    )
    return block_dequant_wire(
        words, scales, pad, block=block, bits=bits, interpret=interpret,
        out_dtype=g.dtype,
    )


def block_dequantize(
    codes: jnp.ndarray, scales: jnp.ndarray, pad: int, *, block: int = 512,
    bits: int = 8, use_kernel: bool = True, interpret: bool | None = None,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    if not use_kernel:
        out = ref.block_dequant_ref(codes, scales, block, bits)
    else:
        interp = (not _on_tpu()) if interpret is None else interpret
        rows = codes.shape[0] // block
        rows_p, br = _row_tiling(rows)
        out = block_dequant_pallas(
            jnp.pad(codes, (0, (rows_p - rows) * block)),
            jnp.pad(scales, (0, rows_p - rows)), block=block, bits=bits,
            block_rows=br, interpret=interp, out_dtype=out_dtype,
        )[: rows * block]
    return out[: codes.shape[0] - pad] if pad else out


# ---------------------------------------------------------------------------
# packed wire dispatchers (DESIGN.md "Wire-format layer")
# ---------------------------------------------------------------------------
#
# Each dispatcher pads to the (rows, WIRE_BLOCK) kernel layout with rows a
# multiple of the row tile (``_row_tiling``) and crops the flat wire back to the exact word count the ledger charges for.  The
# kernels hand words over as int32; the uint32 wire is a bitcast here.
# ``use_kernel=False`` routes to the ref.py oracle -- the two paths are
# bit-exact, which tests/test_wire.py asserts per kernel.

def _to_i32(words: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def _to_u32(words: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def _check_block_wire(bits: int, block: int) -> None:
    if block != ref.WIRE_BLOCK or bits not in (2, 4, 8):
        raise ValueError(
            f"no wire kernel for bits={bits}, block={block} (kernels cover "
            f"bits 2/4/8 at block {ref.WIRE_BLOCK}); use_kernel=False runs "
            "the oracle")


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def sign_wire(
    g: jnp.ndarray, *, use_kernel: bool = True, interpret: bool | None = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """signSGD uplink: flat g (n,) -> (words uint32 (ceil(n/32),), scale ()).

    scale is mean(|g|) via the canonical two-stage reduction
    (ref.mean_abs_ref); the kernel emits the per-row partials and the final
    sum happens here, so both paths share one float reduction tree.
    """
    n = g.shape[0]
    if not use_kernel:
        return ref.sign_pack_ref(g)
    interp = (not _on_tpu()) if interpret is None else interpret
    pad = (-n) % ref.WIRE_BLOCK
    gp = g.astype(jnp.float32)
    if pad:
        gp = jnp.pad(gp, (0, pad))
    rows = gp.shape[0] // ref.WIRE_BLOCK
    g2, tile = _pad_rows(gp.reshape(rows, ref.WIRE_BLOCK))
    words2, rowsums = sign_pack_pallas(g2, block_rows=tile, interpret=interp)
    nw = -(-n // 32)
    # zero pad rows carry zero partials; the oracle's tree sees `rows` rows
    return (_to_u32(words2.reshape(-1)[:nw]),
            ref.pairwise_sum(rowsums[:rows, 0]) / n)


@functools.partial(jax.jit, static_argnames=("n", "use_kernel", "interpret"))
def sign_unwire(
    words: jnp.ndarray, scale: jnp.ndarray, n: int, *,
    use_kernel: bool = True, interpret: bool | None = None,
) -> jnp.ndarray:
    """Inverse: packed sign bits + scale -> (n,) f32 (+scale / -scale)."""
    if not use_kernel:
        return ref.sign_unpack_ref(words, scale, n)
    interp = (not _on_tpu()) if interpret is None else interpret
    wpr = ref.WIRE_BLOCK // 32
    rows = -(-n // ref.WIRE_BLOCK)
    pad = rows * wpr - words.shape[0]
    wp = jnp.pad(words, (0, pad)) if pad else words
    w2, tile = _pad_rows(_to_i32(wp).reshape(rows, wpr))
    out = sign_unpack_pallas(w2, scale, block_rows=tile, interpret=interp)
    return out.reshape(-1)[:n]


def block_quant_wire(
    g: jnp.ndarray, key: jax.Array, *, bits: int = 8, block: int = 512,
    use_kernel: bool = True, interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Fused block-quantize + bit-pack of a flat update (FedPAQ/FedQClip).

    Returns (words uint32, scales (ceil(n/block),) f32, pad).  The fused
    kernel covers ``block == WIRE_BLOCK`` and ``bits in {2, 4, 8}`` (bit
    widths whose codes tile a 512-lane row evenly); other widths need
    ``use_kernel=False`` (the jnp oracle, valid for any bits >= 2 and
    block).  bits == 1 is rejected: the symmetric signed code book has
    2^(bits-1) - 1 = 0 levels there -- a 1-bit wire is the *sign* format
    (``sign_wire``).
    """
    assert bits >= 2, "1-bit quantization is the sign wire (ops.sign_wire)"
    n = g.shape[0]
    pad = (-n) % block
    gp = jnp.pad(g, (0, pad)) if pad else g
    u = jax.random.uniform(key, gp.shape, jnp.float32)
    if not use_kernel:
        words, scales = ref.quant_pack_ref(gp, u, block, bits)
        return words, scales, pad
    _check_block_wire(bits, block)
    interp = (not _on_tpu()) if interpret is None else interpret
    rows = gp.shape[0] // block
    g2, tile = _pad_rows(gp.reshape(rows, block).astype(jnp.float32))
    u2, _ = _pad_rows(u.reshape(rows, block))
    words2, scales = quant_pack_pallas(g2, u2, bits=bits, block_rows=tile,
                                       interpret=interp)
    return _to_u32(words2[:rows].reshape(-1)), scales[:rows, 0], pad


def block_dequant_wire(
    words: jnp.ndarray, scales: jnp.ndarray, pad: int, *, bits: int = 8,
    block: int = 512, use_kernel: bool = True,
    interpret: bool | None = None, out_dtype=jnp.float32,
) -> jnp.ndarray:
    """Inverse wire pass: unpack + un-bias + dequantize, cropping ``pad``."""
    assert bits >= 2, "1-bit codes are the sign wire (ops.sign_unwire)"
    rows = scales.shape[0]
    n_p = rows * block
    if not use_kernel:
        out = ref.unpack_dequant_ref(words, scales, n_p, block, bits)
        out = out.astype(out_dtype)
    else:
        _check_block_wire(bits, block)
        interp = (not _on_tpu()) if interpret is None else interpret
        w2, tile = _pad_rows(_to_i32(words).reshape(rows, -1))
        s2, _ = _pad_rows(scales.reshape(rows, 1))
        out = unpack_dequant_pallas(
            w2, s2, bits=bits, block_rows=tile, interpret=interp,
            out_dtype=out_dtype,
        )[:rows].reshape(-1)
    return out[: n_p - pad] if pad else out


def _scales_from_cols(s3: jnp.ndarray) -> jnp.ndarray:
    """Kernel (nb, k, 1) scale columns -> the wire's (k, nb) scales."""
    return s3[:, :, 0].T


def _scales_to_cols(scales: jnp.ndarray) -> jnp.ndarray:
    return scales.T[:, :, None]


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def coeff_quant(
    A: jnp.ndarray, *, use_kernel: bool = True, interpret: bool | None = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """int8 coefficient wire for a (k, m) matrix: one scale per (row,
    512-column block), deterministic rounding.  Returns (codes int8 (k, m),
    scales (k, ceil(m/512)), ship f32 (k, m))."""
    if not use_kernel:
        return ref.coeff_quant_ref(A)
    interp = (not _on_tpu()) if interpret is None else interpret
    k, m = A.shape
    Ap, pad = _pad_cols(A.astype(jnp.float32), ref.WIRE_BLOCK)
    codes, s3, ship = coeff_quant_pallas(Ap, interpret=interp)
    if pad:
        codes, ship = codes[:, :m], ship[:, :m]
    return codes, _scales_from_cols(s3), ship


@functools.partial(jax.jit, static_argnames=("wire_dtype", "use_kernel", "interpret"))
def coeff_roundtrip(
    A: jnp.ndarray, wire_dtype: str = "f32", *, use_kernel: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Ship a coefficient matrix through its wire format and back.

    "f32" is the identity (exact 32-bit wire), "bf16" pair-packs bitcast
    half-words into uint32 (ref oracle -- a cast plus lossless packing),
    "int8" runs the scaled deterministic quantizer.  Client and server both
    see the returned value, so the two basis mirrors stay in sync.
    """
    if wire_dtype == "f32":
        return A
    if wire_dtype == "bf16":
        words = ref.bf16_pack_ref(A)
        return ref.bf16_unpack_ref(words, A.shape[-1]).astype(A.dtype)
    assert wire_dtype == "int8", f"unknown wire_dtype {wire_dtype!r}"
    _, _, ship = coeff_quant(A, use_kernel=use_kernel, interpret=interpret)
    return ship.astype(A.dtype)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def encode_quant(
    M: jnp.ndarray, G: jnp.ndarray, *, use_kernel: bool = True,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused project + int8 wire: A = M^T G shipped as int8 codes, residual
    against the shipped value (SVDFed's steady-state uplink).

    Returns (codes int8 (k, m), scales (k, ceil(m/512)), E (l, m)).
    """
    if not use_kernel:
        return ref.encode_quant_ref(M, G)
    l, k = M.shape
    if choose_block_m(l, k, G.dtype) < 512:
        raise ValueError(
            f"encode_quant kernel: basis ({l}, {k}) does not fit a 512-column "
            f"tile in the {VMEM_BUDGET_BYTES}-byte VMEM budget; "
            "use_kernel=False runs the oracle")
    interp = (not _on_tpu()) if interpret is None else interpret
    m = G.shape[1]
    Gp, pad = _pad_cols(G, 512)
    codes, s3, E = encode_quant_pallas(M, Gp, interpret=interp)
    if pad:
        codes, E = codes[:, :m], E[:, :m]
    return codes, _scales_from_cols(s3), E


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def decode_wire(
    M: jnp.ndarray, codes: jnp.ndarray, scales: jnp.ndarray, *,
    use_kernel: bool = True, interpret: bool | None = None,
) -> jnp.ndarray:
    """Ghat = M dequant(codes): the server side of the int8 coefficient
    wire, dequantization fused into the reconstruction GEMM."""
    if not use_kernel:
        return ref.decode_ref(M, ref.coeff_dequant_ref(codes, scales))
    interp = (not _on_tpu()) if interpret is None else interpret
    l, k = M.shape
    m = codes.shape[1]
    cp, pad = _pad_cols(codes, 512)
    bl = 256 if l % 256 == 0 else (128 if l % 128 == 0 else l)
    out = decode_wire_pallas(M, cp, _scales_to_cols(scales), block_l=bl,
                             interpret=interp)
    return out[:, :m] if pad else out
