"""Pallas TPU kernel: fused unpack-and-decode for bit-packed codes.

The ``mpe`` scheme (DESIGN.md §13) stores sub-byte quantization codes
bit-packed — (B, W) uint8 words holding ``8 // bits`` codes per byte —
so a bits=2 tail tier reads 4x fewer code bytes from HBM than the
uint8 layout.  Keeping that byte win requires the unpack to happen
*inside* the kernel: an O(n) host/HBM unpack copy before the decode
would read and write the unpacked table and forfeit the reduction.

Per grid step the kernel streams a (Bblk, Wblk) packed block into
VMEM, widens to int32, and per subspace shifts/masks its code out of
its byte lane (little-endian within the byte, matching
``pack.pack_codes``), feeding it straight into the same exact one-hot
MXU matmul as ``mgqe_decode`` — shift/mask are VPU-friendly lane-wise
int ops, so the unpack rides along at register bandwidth.  The unpack
is per subspace with static shifts: a (Bblk, W, 8 // bits) ->
(Bblk, D) reshape is a shape cast Mosaic refuses.

Block layout: grid (B/block_b, D/block_d) with ``block_d`` in SUBSPACE
units.  A subspace tile maps to a byte tile only when it covers whole
bytes, so ``block_d`` must be a multiple of ``8 // bits`` and divide
D; anything else falls back to full width (mirrors ``rq_decode_stages``'s
block_d fallback).  On TPU a partial-width block must also span a
multiple of 128 bytes, so at realistic D only full width compiles.  At
full width the packed block may carry up to ``8 // bits - 1`` pad codes
in its last byte — they are never decoded.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mgqe_decode.mgqe_decode import onehot_dot
from repro.kernels.packed_decode.pack import PACK_BITS, packed_width


def _packed_decode_kernel(packed_ref, cent_ref, out_ref, *, bits):
    packed = packed_ref[...].astype(jnp.int32)        # (Bblk, Wblk)
    dblk, k, s = cent_ref.shape
    per_byte = 8 // bits
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    for j in range(dblk):
        byte = packed[:, j // per_byte:j // per_byte + 1]
        code = (byte >> ((j % per_byte) * bits)) & (2 ** bits - 1)
        dec = onehot_dot(code == slot, cent_ref[j])   # (Bblk, S)
        out_ref[:, j * s:(j + 1) * s] = dec.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bits", "block_b", "block_d",
                                    "interpret"))
def packed_decode(packed: jax.Array, centroids: jax.Array, bits: int,
                  block_b: int = 256, block_d: Optional[int] = None,
                  interpret: bool = False) -> jax.Array:
    """packed (B, W) uint8; centroids (D, K, S); W = ceil(D/(8//bits))
    -> (B, D*S) float32, decoding without ever materializing unpacked
    codes outside VMEM.

    block_b: rows per grid step (batch padded to it).  block_d: subspaces
    per grid step — must divide D and be a multiple of ``8 // bits``
    (byte-aligned tiles), else full width.  VMEM working set per step is
    the ``mgqe_decode`` one with codes at 1/(8//bits) the bytes:
    Bblk*Wblk packed + dblk*K*S*4 centroids + Bblk*K*4 one-hot.
    """
    if bits not in PACK_BITS:
        raise ValueError(f"bits must be one of {PACK_BITS}, got {bits}")
    b, w = packed.shape
    n_sub, k, s = centroids.shape
    per_byte = 8 // bits
    if w != packed_width(n_sub, bits):
        raise ValueError(
            f"packed width {w} does not hold {n_sub} codes of {bits} "
            f"bits (want {packed_width(n_sub, bits)})")
    if block_d is None or n_sub % block_d or block_d % per_byte:
        block_d = n_sub
    wblk = w if block_d == n_sub else block_d // per_byte
    pad = (-b) % block_b
    if pad:
        packed = jnp.pad(packed, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_packed_decode_kernel, bits=bits),
        grid=((b + pad) // block_b, n_sub // block_d),
        in_specs=[
            pl.BlockSpec((block_b, wblk), lambda i, j: (i, j)),
            pl.BlockSpec((block_d, k, s), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_d * s),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b + pad, n_sub * s),
                                       centroids.dtype),
        interpret=interpret,
    )(packed, centroids)
    return out[:b]
