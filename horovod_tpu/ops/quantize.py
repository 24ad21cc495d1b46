"""Block-scaled quantization codecs for the device plane (traced/XLA path).

This is the in-``jit`` mirror of the host ring's block-scaled wire codecs
(``cpp/wire_codec.h``): the same block geometry, the same scale rules, and
the same all-zero / non-finite-block handling, so a tensor quantized on the
device plane decodes to the values the host codec would have produced (to
the scale's last place inside a compiled program: see the divides below).
EQuARX (PAPERS.md) is the design reference: block-scaled codes inside the
XLA program keep the compression on-chip — no host transfers — while fp32
accumulation between hops preserves reduction accuracy.

Two device codecs:

- ``int8``: one fp32 scale per 256-element block, ``scale = max|x| / 127``.
- ``int4``: the same block scale with 4-bit codes packed two per byte
  (``scale = max|x| / WIRE_INT4_MAX``); on the wire this is ~0.13x raw.

Layout: a flat fp32 tensor is viewed as ``[nblocks, WIRE_BLOCK]`` (the last
block zero-padded; zeros cannot raise ``max|x|``, so a short last block
quantizes exactly as the byte-stream codec quantizes it).  Quantization
yields a code array plus one fp32 scale per block — together the traced
analog of the wire stream's records, and what actually rides
``lax.ppermute`` between devices.

The kernels are Pallas with the same dispatch rules as
``ops/flash_attention.py``: on TPU the Pallas kernel runs natively,
off-TPU the public entry points fall back to an identical-math jnp
implementation, and ``interpret=True`` forces the kernels through the
Pallas interpreter (tests).  Scale/inv divides are computed OUTSIDE the
kernels (XLA's fp32 divide is correctly rounded, matching the C++ side;
the Pallas interpreter's is not), and the int4 nibble pack/unpack is exact
integer math in plain jnp.  Correctly rounded is what a divide dispatched by
itself is.  Inside one compiled program XLA's algebraic simplifier, for the
CPU and the TPU alike, states ``max|x| / 127`` and ``max|x| / 7`` as a
multiply by the rounded reciprocal, so a compiled scale can differ from
WireEncode's in its last place (21 / 7 reads 3.0000002).  Encode and decode
share one scale array, so the ring agrees with itself; only an exact
comparison with the C++ stream or a plain psum sees it (PERF.md section 7).

Byte accounting: every quantized collective calls :func:`note_device_bytes`
with the raw-vs-encoded wire byte counts so the realized compression ratio
is observable (``data_plane_stats()['device_raw'/'device_encoded']``,
``hvd.metrics()``, Prometheus).
"""

from __future__ import annotations

import math
import threading
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# --- Block geometry and codec ids: MUST mirror cpp/wire_codec.h ----------
# (tools/hvd_lint.py's wire-codec pass checks these against the header; a
# drift fails lint.)
WIRE_BLOCK = 256           # kWireBlock: elements per scale record
WIRE_SCALE_BYTES = 4       # kWireScaleBytes: little-endian fp32 scale
WIRE_INT4_MAX = 7          # kWireInt4Max: int4 code clamp bound
WIRE_CODEC_IDS = {"none": 0, "bf16": 1, "int8": 2, "int4": 3}
# Codecs the device plane can engage.  bf16 stays host-only: on-chip the
# bf16 cast is a plain convert_element_type XLA already fuses — only the
# block-scaled codecs need an implementation here.
DEVICE_WIRE_CODECS = ("none", "int8", "int4")

# Rows per Pallas grid step: 32 sublanes satisfies the int8 (32, 128) and
# fp32 (8, 128) minimum tile constraints simultaneously (WIRE_BLOCK = 256
# lanes is a multiple of 128).
_QUANT_ROWS = 32


def encoded_nbytes(count: int, codec: str = "int8") -> int:
    """Wire bytes for ``count`` fp32 elements under ``codec`` — the same
    formula as WireEncodedBytes."""
    count = int(count)
    blocks = -(-count // WIRE_BLOCK)
    if codec == "none":
        return 4 * count
    if codec == "bf16":
        return 2 * count
    if codec == "int4":
        return blocks * WIRE_SCALE_BYTES + (count + 1) // 2
    return blocks * WIRE_SCALE_BYTES + count


def torus_factors(world: int) -> Optional[Tuple[int, int]]:
    """Near-square 2-D factorization ``(a, b)`` of ``world`` with
    ``2 <= a <= b`` and ``a`` maximal (a = major/outer axis, b =
    minor/inner axis).  None when ``world`` is prime or < 4 — the torus
    schedule then demotes to a 1-D ring."""
    world = int(world)
    if world < 4:
        return None
    a = int(math.isqrt(world))
    while a >= 2:
        if world % a == 0:
            return (a, world // a)
        a -= 1
    return None


def ring_bytes(count: int, world: int, codec: str = "int8",
               schedule: str = "ring") -> Tuple[int, int]:
    """Per-rank (raw, encoded) wire bytes for one quantized allreduce of
    ``count`` fp32 elements over ``world`` ranks under ``schedule``:

    - ``ring``: reduce-scatter plus all-gather, world-1 hops each, one
      chunk of ``ceil(count/world)`` elements per hop.
    - ``bidi``: same hop count but each hop carries two half chunks, one
      per ICI direction, so per-link bytes per hop halve (totals per rank
      are schedule-identical up to short-block scale overhead).
    - ``torus`` (a x b factorization): 2(b-1) hops of ``ceil(count/b)``
      along the minor axis plus 2(a-1) hops of ``ceil(ceil(count/b)/a)``
      along the major axis — O(a+b) chunk-hops instead of O(ab).
    """
    world = max(1, int(world))
    count = int(count)
    if world == 1:
        return (0, 0)
    if schedule == "torus":
        f = torus_factors(world)
        if f is not None:
            a, b = f
            c1 = -(-count // b)
            c2 = -(-c1 // a)
            h1 = 2 * (b - 1)
            h2 = 2 * (a - 1)
            return (4 * (h1 * c1 + h2 * c2),
                    h1 * encoded_nbytes(c1, codec) +
                    h2 * encoded_nbytes(c2, codec))
        schedule = "bidi"          # prime/small world: torus -> bidi
    chunk = -(-count // world)
    hops = 2 * (world - 1)
    if schedule == "bidi" and chunk >= 2:
        front = chunk // 2
        back = chunk - front
        return (hops * chunk * 4,
                hops * (encoded_nbytes(front, codec) +
                        encoded_nbytes(back, codec)))
    return (hops * chunk * 4, hops * encoded_nbytes(chunk, codec))


# --- Device-plane byte counters ------------------------------------------

_DEV_LOCK = threading.Lock()
_DEV_RAW = 0
_DEV_ENCODED = 0
_NATIVE_SINK: Optional[Callable[[int, int], None]] = None


def set_native_byte_sink(fn: Optional[Callable[[int, int], None]]) -> None:
    """Register a callable forwarding (raw, encoded) deltas to the native
    metrics registry (NativeCore wires hvd_device_plane_note here) so the
    counters show up in hvd.metrics() / Prometheus."""
    global _NATIVE_SINK
    _NATIVE_SINK = fn


def note_device_bytes(raw: int, encoded: int) -> None:
    global _DEV_RAW, _DEV_ENCODED
    with _DEV_LOCK:
        _DEV_RAW += int(raw)
        _DEV_ENCODED += int(encoded)
    sink = _NATIVE_SINK
    if sink is not None:
        try:
            sink(int(raw), int(encoded))
        except Exception:
            pass


def device_byte_counters() -> Tuple[int, int]:
    with _DEV_LOCK:
        return (_DEV_RAW, _DEV_ENCODED)


def reset_device_byte_counters() -> None:
    global _DEV_RAW, _DEV_ENCODED
    with _DEV_LOCK:
        _DEV_RAW = 0
        _DEV_ENCODED = 0


# --- Block-form reference implementation (identical math to WireEncode) --

def _block_scales(xb, qmax: float = 127.0):
    """Per-block (scale, inv) mirroring WireEncode(kInt8/kInt4)
    bit-for-bit:

    - max|x| scans with ``a > maxabs`` so NaN elements never win the max
      (an all-NaN block keeps scale 0 and encodes zeros);
    - a block whose max is inf gets a non-finite scale -> codes all zero
      (the stored scale stays inf, so decode flags the block as NaN rather
      than inventing values).

    ``inv`` is 0 exactly for the all-zero / non-finite blocks (a finite
    positive scale can never reciprocate to 0 in fp32), so ``inv > 0`` is
    the block-ok predicate downstream.  Computed in plain jnp — XLA's
    fp32 divide is correctly rounded, matching the C++ divides (dispatched
    alone; compiled, ``/ qmax`` is a multiply by a rounded reciprocal: the
    module's head); the Pallas interpreter's is not, which is why the
    divides live outside the kernel.
    """
    absx = jnp.abs(xb)
    maxabs = jnp.max(jnp.where(jnp.isnan(absx), 0.0, absx),
                     axis=1, keepdims=True)
    scale = maxabs / qmax
    ok = (scale > 0.0) & jnp.isfinite(scale)
    inv = jnp.where(ok, 1.0 / jnp.where(ok, scale, 1.0), 0.0)
    return scale.astype(jnp.float32), inv.astype(jnp.float32)


def _quantize_codes_ref(xb, inv, qmax: float = 127.0):
    """Elementwise half of WireEncode: round, clamp, block gate.

    Clamping uses std::min/std::max operand order, under which a NaN
    element inside an otherwise-finite block lands on +qmax (exactly what
    the C++ loop produces)."""
    v = jnp.round(xb * inv)
    v = jnp.where(v < qmax, v, qmax)        # std::min(qmax, v): NaN -> qmax
    v = jnp.where(v > -qmax, v, -qmax)      # std::max(-qmax, v)
    return jnp.where(inv > 0.0, v, 0.0).astype(jnp.int8)


def _quantize_blocks_ref(xb):
    """jnp mirror of WireEncode(kInt8) on [nblocks, WIRE_BLOCK] fp32."""
    scale, inv = _block_scales(xb)
    return _quantize_codes_ref(xb, inv), scale


def _dequantize_blocks_ref(qb, scales):
    """jnp mirror of WireDecodeRange: scale * code, in fp32."""
    return scales.astype(jnp.float32) * qb.astype(jnp.float32)


# --- int4 nibble packing (exact integer jnp, shared by every backend) -----

def _pack_int4(codes):
    """[nblocks, WIRE_BLOCK] int8 codes in [-7, 7] -> [nblocks,
    WIRE_BLOCK/2] packed bytes: element 2i in the low nibble, 2i+1 in the
    high nibble, all arithmetic on uint8 (mod-256, matching the C++
    encoder's unsigned pack)."""
    u = codes.astype(jnp.uint8)
    lo = u[:, 0::2] & 0x0F
    hi = u[:, 1::2] & 0x0F
    return (lo | (hi << 4)).astype(jnp.int8)


def _unpack_int4(packed):
    """Inverse of :func:`_pack_int4`: sign-extend each nibble via the
    ``(nib ^ 8) - 8`` trick (identical to WireDecodeRange(kInt4))."""
    b = packed.astype(jnp.uint8).astype(jnp.int32)
    lo = ((b & 0x0F) ^ 8) - 8
    hi = (((b >> 4) & 0x0F) ^ 8) - 8
    nb = packed.shape[0]
    return jnp.stack([lo, hi], axis=-1).reshape(nb, WIRE_BLOCK).astype(
        jnp.int8)


# --- Pallas kernels -------------------------------------------------------

def _quant_kernel(x_ref, inv_ref, q_ref):
    # Elementwise only (mul/round/compare/select are exactly rounded on
    # every backend, so interpret mode is bit-identical to the jnp
    # fallback); the per-block scale/inv reduction rides in from jnp.
    x = x_ref[...]                                    # [ROWS, WIRE_BLOCK]
    inv = inv_ref[...]                                # [ROWS, 1]
    v = jnp.round(x * inv)
    v = jnp.where(v < 127.0, v, 127.0)
    v = jnp.where(v > -127.0, v, -127.0)
    q_ref[...] = jnp.where(inv > 0.0, v, 0.0).astype(jnp.int8)


def _quant_kernel_int4(x_ref, inv_ref, q_ref):
    qmax = float(WIRE_INT4_MAX)
    x = x_ref[...]
    inv = inv_ref[...]
    v = jnp.round(x * inv)
    v = jnp.where(v < qmax, v, qmax)
    v = jnp.where(v > -qmax, v, -qmax)
    q_ref[...] = jnp.where(inv > 0.0, v, 0.0).astype(jnp.int8)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = s_ref[...] * q_ref[...].astype(jnp.float32)


def _pad_rows(xb, rows: int):
    nb = xb.shape[0]
    nb_pad = -(-nb // rows) * rows
    if nb_pad != nb:
        xb = jnp.pad(xb, ((0, nb_pad - nb), (0, 0)))
    return xb, nb


def _quantize_codes_pallas(xb, inv, interpret: bool, qmax: float):
    xb, nb = _pad_rows(xb, _QUANT_ROWS)
    inv_p, _ = _pad_rows(inv, _QUANT_ROWS)
    grid = (xb.shape[0] // _QUANT_ROWS,)
    kernel = _quant_kernel if qmax == 127.0 else _quant_kernel_int4
    q = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((_QUANT_ROWS, WIRE_BLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((_QUANT_ROWS, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_QUANT_ROWS, WIRE_BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xb.shape[0], WIRE_BLOCK), jnp.int8),
        interpret=interpret,
    )(xb, inv_p)
    return q[:nb]


def _quantize_blocks_pallas(xb, interpret: bool):
    scale, inv = _block_scales(xb)
    return _quantize_codes_pallas(xb, inv, interpret, 127.0), scale


def _dequantize_blocks_pallas(qb, scales, interpret: bool):
    qb, nb = _pad_rows(qb, _QUANT_ROWS)
    scales, _ = _pad_rows(scales, _QUANT_ROWS)
    grid = (qb.shape[0] // _QUANT_ROWS,)
    x = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((_QUANT_ROWS, WIRE_BLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((_QUANT_ROWS, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_QUANT_ROWS, WIRE_BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((qb.shape[0], WIRE_BLOCK),
                                       jnp.float32),
        interpret=interpret,
    )(qb, scales)
    return x[:nb]


def _dispatch(interpret: Optional[bool]):
    """flash_attention's dispatch rule: None -> Pallas on TPU, jnp fallback
    elsewhere; True forces the Pallas interpreter (tests)."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return None          # identical-math jnp fallback
        return False             # native Pallas
    return bool(interpret)


# --- Public block-form API ------------------------------------------------

def quantize_blocks(xb, interpret: Optional[bool] = None):
    """[nblocks, WIRE_BLOCK] fp32 -> (int8 codes, fp32 [nblocks, 1] scales)."""
    mode = _dispatch(interpret)
    if mode is None:
        return _quantize_blocks_ref(xb)
    return _quantize_blocks_pallas(xb, mode)


def dequantize_blocks(qb, scales, interpret: Optional[bool] = None):
    mode = _dispatch(interpret)
    if mode is None:
        return _dequantize_blocks_ref(qb, scales)
    return _dequantize_blocks_pallas(qb, scales, mode)


def _to_blocks(flat):
    n = flat.shape[0]
    nblocks = max(1, -(-n // WIRE_BLOCK))
    pad = nblocks * WIRE_BLOCK - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(nblocks, WIRE_BLOCK)


def quantize(flat, codec: str = "int8", interpret: Optional[bool] = None):
    """Flat fp32 [n] -> (codes, scales) under ``codec``:

    - ``int8``: codes [nblocks, WIRE_BLOCK] int8, scales [nblocks, 1] fp32.
    - ``int4``: codes [nblocks, WIRE_BLOCK/2] int8 (packed nibbles),
      scales [nblocks, 1] fp32.

    The short last block is zero-padded, which cannot change its max|x| —
    identical to the byte codec's short-block rule.  The (codes, scales)
    pair is a pytree of same-shape-per-rank arrays, so collectives move it
    with ``tree_map``'d ``lax.ppermute``/``all_gather``.
    """
    xb = _to_blocks(flat.astype(jnp.float32))
    mode = _dispatch(interpret)
    if codec == "int4":
        scale, inv = _block_scales(xb, float(WIRE_INT4_MAX))
        if mode is None:
            codes = _quantize_codes_ref(xb, inv, float(WIRE_INT4_MAX))
        else:
            codes = _quantize_codes_pallas(xb, inv, mode,
                                           float(WIRE_INT4_MAX))
        return _pack_int4(codes), scale
    if mode is None:
        return _quantize_blocks_ref(xb)
    return _quantize_blocks_pallas(xb, mode)


def dequantize(qb, scales, count: int, codec: str = "int8",
               interpret: Optional[bool] = None):
    """Inverse of :func:`quantize`: back to flat fp32 [count]."""
    if codec == "int4":
        qb = _unpack_int4(qb)
    xb = dequantize_blocks(qb, scales, interpret)
    return xb.reshape(-1)[:count]


def fake_quantize(x, codec: str = "int8",
                  interpret: Optional[bool] = None):
    """dequantize(quantize(x)) with x's shape — the local quantization
    image used by error feedback (residual = x - fake_quantize(x))."""
    flat = x.reshape(-1)
    qb, s = quantize(flat, codec, interpret)
    return dequantize(qb, s, flat.shape[0], codec, interpret).reshape(x.shape)
