"""Compressed delta transport (DESIGN.md §13).

Client deltas cross every boundary in this system — client→server,
pod→pod, buffered inside FedBuff — and until now always as full f32
vectors. This module defines the wire representation: per-block-scaled
int8 (one f32 scale per :data:`QBLOCK` elements) or a bf16 recast, both
carried in a :class:`CompressedDelta` alongside the true (unpadded)
element count. Quantization error is absorbed by client-side
error-feedback residuals (``Client._residual``): what the server never
received is folded into the client's *next* delta, so the error stays
bounded instead of accumulating across rounds.

``CompressedDelta`` is deliberately NOT registered as a jax pytree:
generic ``pt.tree_*`` helpers must fail loudly on a compressed delta
rather than silently treating ``q`` as parameters. Servers decompress
explicitly (pytree backends) or hand ``q``/``scales`` straight to the
fedagg sweeps (``scales=``), which dequantize one VMEM tile at a time.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fedagg.fedagg import BLOCK_ROWS, LANES, QBLOCK
from repro.utils import pytree as pt

# Flat staging layout shared with the kernels: vectors are zero-padded to a
# multiple of the full VMEM tile so every grid step sees whole blocks.
BLOCK = BLOCK_ROWS * LANES

MODES = ("off", "int8", "bf16")


@dataclass
class CompressedDelta:
    """A client delta in transport form.

    ``mode``   "int8" or "bf16".
    ``q``      the payload: int8 (n_padded,) for int8 mode, bf16 (n_padded,)
               for bf16 mode. Always padded to a multiple of :data:`BLOCK`.
    ``scales`` f32 (n_padded // QBLOCK,) per-block scales for int8 mode;
               ``None`` for bf16.
    ``n``      true element count before padding (``FlatSpec.n``).
    """

    mode: str
    q: jax.Array
    scales: jax.Array | None
    n: int

    def wire_bytes(self) -> int:
        """Bytes this delta occupies in transport form."""
        total = self.q.size * self.q.dtype.itemsize
        if self.scales is not None:
            total += self.scales.size * self.scales.dtype.itemsize
        return int(total)


@jax.jit
def _quantize_int8(vec: jax.Array):
    """f32 (n,) -> (int8 (n,), f32 (n // QBLOCK,)) per-block absmax scales.

    scale = absmax / 127 per QBLOCK elements; all-zero blocks get scale 0
    and quantize (exactly) to zeros via the inv-scale-0 trick.
    """
    blocks = vec.reshape(-1, QBLOCK)
    absmax = jnp.max(jnp.abs(blocks), axis=1)
    scales = absmax / 127.0
    inv = jnp.where(scales > 0, 1.0 / jnp.where(scales > 0, scales, 1.0), 0.0)
    q = jnp.clip(jnp.round(blocks * inv[:, None]), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scales


@jax.jit
def _dequantize_int8(q: jax.Array, scales: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32).reshape(-1, QBLOCK)
            * scales[:, None]).reshape(-1)


def quantize_vec(vec: jax.Array, mode: str, n: int) -> CompressedDelta:
    """Compress a padded flat f32 vector into transport form.

    ``vec`` must already be padded to a multiple of :data:`BLOCK` (the
    ``FlatSpec`` staging layout); ``n`` is the true element count.
    """
    assert vec.shape[0] % BLOCK == 0, (vec.shape, BLOCK)
    if mode == "int8":
        q, scales = _quantize_int8(vec)
        return CompressedDelta("int8", q, scales, n)
    if mode == "bf16":
        return CompressedDelta("bf16", vec.astype(jnp.bfloat16), None, n)
    raise ValueError(f"unknown compression mode {mode!r}")


def dequantize(cd: CompressedDelta) -> jax.Array:
    """Transport form -> padded flat f32 vector (the jnp reference path)."""
    if cd.mode == "int8":
        return _dequantize_int8(cd.q, cd.scales)
    if cd.mode == "bf16":
        return cd.q.astype(jnp.float32)
    raise ValueError(f"unknown compression mode {cd.mode!r}")


def is_compressed(delta) -> bool:
    return isinstance(delta, CompressedDelta)


def delta_norm(delta) -> float:
    """l2 norm of a delta in either form (what screening measures).

    For compressed deltas this is the norm of the DEQUANTIZED values —
    the same values aggregation applies — so the defense layer and the
    kernels agree on what each arrival weighs.
    """
    if is_compressed(delta):
        return float(jnp.linalg.norm(dequantize(delta)))
    return float(pt.tree_norm(delta))


def scale_delta(delta, s: float):
    """Scale a delta by ``s`` in its native form (norm-clip verdicts).

    int8 scaling is exact on the scales: dequant(q, s * scales) ==
    s * dequant(q, scales), so clipping never re-quantizes.
    """
    if is_compressed(delta):
        if delta.mode == "int8":
            return CompressedDelta("int8", delta.q,
                                   delta.scales * jnp.float32(s), delta.n)
        return CompressedDelta("bf16",
                               (delta.q.astype(jnp.float32) * s
                                ).astype(jnp.bfloat16), None, delta.n)
    return pt.tree_scale(delta, s)


def wire_bytes_per_param(mode: str) -> float:
    """Average transport bytes per parameter element for ``mode``.

    int8: 1 payload byte + one f32 scale amortized over QBLOCK elements.
    Mirrored (import-free) by ``configs.shapes.delta_wire_bytes``.
    """
    if mode == "int8":
        return 1.0 + 4.0 / QBLOCK
    if mode == "bf16":
        return 2.0
    return 4.0
