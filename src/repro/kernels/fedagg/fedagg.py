"""Fused AsyncFedED aggregation kernels (the paper's server hot spot).

For a 70B-parameter model the jnp reference makes four HBM passes
(read x_t & x_stale for the distance, read delta for the norm, read x_t &
delta again for the AXPY). These kernels do it in two single-pass phases:

  phase 1  fedagg_norms : one pass reading (x_t, x_stale, delta) tiles into
           VMEM, emitting per-block partial sums of ||x_t - x_stale||^2 and
           ||delta||^2  -> host combines to gamma, eta (Eq. 6/7, scalars).
  phase 2  fedagg_axpy  : one pass computing x_t + eta * delta (Eq. 5).

Tiling: the flattened parameter vector is reshaped to (rows, 128) and
swept BLOCK_ROWS rows per grid step, with zero padding to a multiple of
BLOCK. Padding contributes 0 to both sums and is sliced off after the AXPY.
Every block the kernels read or write satisfies the Mosaic (TPU) rule: its
last two dimensions are (8, 128)-aligned or span the whole array. Partial
sums therefore leave each grid step as one lane-dense (8, 128) tile per
quantity (or a whole-array-width (B, B) / (B, 1) block in the batched
sweeps), and the wrapper finishes the reduction.

Kernel mode follows the platform (:func:`resolve_interpret`): the Pallas
interpreter on CPU, compiled Mosaic on TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one grid step processes BLOCK_ROWS x 128 elements resident in VMEM
LANES = 128
SUBLANES = 8                           # f32 vreg / VMEM tile: (8, 128)
BLOCK_ROWS = 512                       # 512*128*4B = 256 KiB per operand tile

# compressed-delta transport (DESIGN.md §13): one f32 scale per QBLOCK
# int8 elements. QBLOCK_ROWS divides every rows-per-step the row schedule
# can pick (the halving ladder floors at 8), so a VMEM tile always holds a
# whole number of scale blocks and dequantization stays one broadcast
# multiply per scale block.
QBLOCK_ROWS = 8
QBLOCK = QBLOCK_ROWS * LANES           # 1024 elements per int8 scale


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The one place the kernel mode is decided. ``None`` (every default
    on the main path) follows the process's default backend at call time:
    the Pallas interpreter on CPU, where the tests run, and compiled
    Mosaic on TPU. Any other backend raises — there is no silent fallback
    to the interpreter. An explicit bool wins (a compile for a described
    TPU from a CPU host passes ``False``)."""
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"fedagg kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {platform!r}")


def _f32(x: jax.Array) -> jax.Array:
    """Upcast to f32 accumulation dtype; compile-time no-op for f32 tiles
    (skipping the convert keeps interpret-mode op counts down)."""
    return x if x.dtype == jnp.float32 else x.astype(jnp.float32)


def _fold(x: jax.Array) -> jax.Array:
    """Sum a (rows, LANES) f32 tile down to one (SUBLANES, LANES) tile: a
    tile-aligned sublane split plus elementwise vreg adds, so per-step
    partial sums leave the kernel lane-dense."""
    return x.reshape(-1, SUBLANES, LANES).sum(axis=0)


def _partials(g: int):
    """Out spec + shape of one per-step (SUBLANES, LANES) partial-sum tile
    per grid step; the wrapper sums the whole array."""
    return (pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
            jax.ShapeDtypeStruct((g * SUBLANES, LANES), jnp.float32))


# operand bytes per grid step of the multi-delta kernels (one buffer of
# each resident input tile)
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024
# scoped-VMEM ceiling granted to the multi-delta sweeps. Double buffering
# plus the in-kernel f32 drift / dequant / flattened copies take the Gram
# sweep at the knees past the compiler's default 16 MiB scope: compiled
# for v5e it needs more than 32 and at most 48 MiB, so 64 leaves margin
# inside the core's 128 MiB of VMEM.
_BATCHED_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _batched_params(interpret: bool):
    return (None if interpret else
            pltpu.CompilerParams(vmem_limit_bytes=_BATCHED_VMEM_LIMIT_BYTES))


def batched_b_max(delta_bytes: int = 4) -> int:
    """Largest batch B for which the multi-delta kernels keep the full
    BLOCK_ROWS tile per grid step — the knee of the B-dependent VMEM row
    schedule below. Beyond it ``_batched_rows`` starts halving rows, so a
    bigger burst buys fewer steps per delta but more steps overall; the
    auto-window controller targets this as its free-batch ceiling.

    ``delta_bytes`` is the per-element width of the resident delta tiles
    (4 = f32, 2 = bf16, 1 = int8 via the quantization-fused kernels): a
    grid step holds one f32 x_t tile, B f32 stale tiles, and B delta tiles
    at that width, so compressed deltas push the knee out — 15 (f32) ->
    20 (bf16) -> 24 (int8) concurrent arrivals at full tile size.
    """
    per_elem = _VMEM_BUDGET_BYTES // (BLOCK_ROWS * LANES)
    return int((per_elem - 4) // (4 + delta_bytes))


def _batched_rows(b: int, n: int, interpret: bool,
                  delta_bytes: int = 4) -> int:
    """Rows per grid step for the multi-delta kernels.

    Compiled (TPU): halved from BLOCK_ROWS — staying a divisor, so
    BLOCK-padded inputs still tile evenly — until the resident operand
    tiles (one f32 x_t tile + B f32 stale tiles + B delta tiles at
    ``delta_bytes`` per element; int8 scale rows are noise) fit the VMEM
    budget; up to B = ``batched_b_max(delta_bytes)`` the full BLOCK_ROWS
    tile fits and the batched sweep runs 1/B the steps of the
    one-at-a-time loop. The floor stays QBLOCK_ROWS so quantized tiles
    always hold whole scale blocks.
    Interpreted (CPU): the grid models no real memory and the emulator pays
    roughly (total operand bytes) per grid step, so run the whole sweep as
    ONE step. The kernel math is tile-count invariant (tests sweep several
    block counts against the jnp oracle).
    """
    if interpret:
        return n // LANES
    bb = max(b, 1)
    per_elem = (bb + 1) * 4 + bb * delta_bytes
    rows = BLOCK_ROWS
    while rows > QBLOCK_ROWS and rows * LANES * per_elem > _VMEM_BUDGET_BYTES:
        rows //= 2
    return rows


def _norms_kernel(xt_ref, xs_ref, d_ref, dist_ref, dn_ref):
    diff = _f32(xt_ref[...]) - _f32(xs_ref[...])
    d = _f32(d_ref[...])
    dist_ref[...] = _fold(diff * diff)
    dn_ref[...] = _fold(d * d)


def fedagg_norms(x_t: jax.Array, x_stale: jax.Array, delta: jax.Array,
                 *, interpret: Optional[bool] = None) -> jax.Array:
    """Inputs: flat (n,) arrays (zero-padded to BLOCK multiple by ops.py).
    Returns (2,) f32: [||x_t - x_stale||^2, ||delta||^2]."""
    n = x_t.shape[0]
    block = BLOCK_ROWS * LANES
    assert n % block == 0, (n, block)
    g = n // block
    shaped = lambda a: a.reshape(g * BLOCK_ROWS, LANES)
    spec, shape = _partials(g)
    dist, dn = pl.pallas_call(
        _norms_kernel,
        grid=(g,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))] * 3,
        out_specs=[spec, spec],
        out_shape=[shape, shape],
        name="fedagg_norms",
        interpret=resolve_interpret(interpret),
    )(shaped(x_t), shaped(x_stale), shaped(delta))
    return jnp.stack([jnp.sum(dist), jnp.sum(dn)])


def _axpy_kernel(eta_ref, xt_ref, d_ref, out_ref):
    eta = eta_ref[0, 0]
    out_ref[...] = (xt_ref[...].astype(jnp.float32)
                    + eta * d_ref[...].astype(jnp.float32)
                    ).astype(out_ref.dtype)


def fedagg_axpy(x_t: jax.Array, delta: jax.Array, eta: jax.Array,
                *, interpret: Optional[bool] = None,
                in_place: bool = False) -> jax.Array:
    """x_t + eta * delta, flat (n,) blocked through VMEM. eta: scalar.

    ``in_place`` writes the result into ``delta``'s buffer (same dtype as
    ``x_t``): each grid step reads and writes the same tile. It saves the
    output's allocation only where the caller's jit donates ``delta``;
    without donation XLA copies ``delta`` first."""
    n = x_t.shape[0]
    block = BLOCK_ROWS * LANES
    assert n % block == 0, (n, block)
    assert not in_place or delta.dtype == x_t.dtype, (delta.dtype, x_t.dtype)
    g = n // block
    shaped = lambda a: a.reshape(g * BLOCK_ROWS, LANES)
    out = pl.pallas_call(
        _axpy_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),          # eta broadcast
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g * BLOCK_ROWS, LANES), x_t.dtype),
        input_output_aliases={2: 0} if in_place else {},
        name="fedagg_axpy",
        interpret=resolve_interpret(interpret),
    )(eta.reshape(1, 1).astype(jnp.float32), shaped(x_t), shaped(delta))
    return out.reshape(n)


def _nt_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b.T`` contracting the lane axis, at full f32 precision: the
    MXU's default for f32 operands is one bf16 pass (~2e-3 relative on
    v5e), too coarse for the sequential-equivalence schedule."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _gram_partials(xt, xs, d, dist_ref, dn_ref, c_ref, g_ref):
    """Multi-delta phase 1 on one f32 tile of x_t (rows, LANES) against B
    stacked (stale, delta) tiles (B, rows, LANES). Beyond the per-update
    norms, emits the cross terms needed to make the batched apply
    *sequentially equivalent* (DESIGN.md §4.3):

        dist_ref[b] = ||x_t - x_stale_b||^2   (partial, (B, 1))
        dn_ref[b]   = ||delta_b||^2           (partial, (B, 1))
        c_ref[b,k]  = <x_t - x_stale_b, delta_k>
        g_ref[k,l]  = <delta_k, delta_l>

    The two Gram blocks go through the MXU as (B, tile) x (B, tile)
    contractions.
    """
    b = d.shape[0]
    d = d.reshape(b, -1)
    s = (xt[None] - xs).reshape(b, -1)              # drift vectors
    c_ref[0] = _nt_dot(s, d)
    g_ref[0] = _nt_dot(d, d)
    dist_ref[0] = jnp.sum(s * s, axis=1, keepdims=True)
    dn_ref[0] = jnp.sum(d * d, axis=1, keepdims=True)


def _norms_batched_kernel(xt_ref, xs_ref, d_ref, *out_refs):
    _gram_partials(_f32(xt_ref[...]), _f32(xs_ref[...]), _f32(d_ref[...]),
                   *out_refs)


def _gram_outputs(g: int, b: int):
    """Out specs + shapes of the four per-step Gram partials; every block
    spans its array's last two dims, (B, 1) or (B, B)."""
    specs = [pl.BlockSpec((1, b, 1), lambda i: (i, 0, 0))] * 2 + [
        pl.BlockSpec((1, b, b), lambda i: (i, 0, 0))] * 2
    shapes = [jax.ShapeDtypeStruct((g, b, 1), jnp.float32)] * 2 + [
        jax.ShapeDtypeStruct((g, b, b), jnp.float32)] * 2
    return specs, shapes


def _sum_gram(dist, dn, c, gram):
    return (jnp.sum(dist, axis=0)[:, 0], jnp.sum(dn, axis=0)[:, 0],
            jnp.sum(c, axis=0), jnp.sum(gram, axis=0))


def fedagg_norms_batched(x_t: jax.Array, x_stales: jax.Array,
                         deltas: jax.Array, *,
                         interpret: Optional[bool] = None):
    """Batched phase 1 over B concurrent arrivals in ONE grid sweep.

    Inputs: x_t (n,), x_stales (B, n), deltas (B, n); n a BLOCK multiple
    (zero-padded by ops.py — padding contributes 0 to every sum).
    Returns (dist0_sq (B,), dn_sq (B,), cross (B, B), gram (B, B)) f32,
    summed over blocks. Each grid step keeps (2B+1) operand tiles resident,
    so rows-per-step shrinks with B past the knee to bound VMEM.
    """
    interpret = resolve_interpret(interpret)
    b, n = deltas.shape
    assert x_t.shape == (n,) and x_stales.shape == (b, n)
    rows = _batched_rows(b, n, interpret, deltas.dtype.itemsize)
    block = rows * LANES
    assert n % (BLOCK_ROWS * LANES) == 0, (n, BLOCK_ROWS * LANES)
    g = n // block
    shaped1 = lambda a: a.reshape(g * rows, LANES)
    shapedb = lambda a: a.reshape(b, g * rows, LANES)
    specs, shapes = _gram_outputs(g, b)
    out = pl.pallas_call(
        _norms_batched_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((b, rows, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((b, rows, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=specs,
        out_shape=shapes,
        compiler_params=_batched_params(interpret),
        name="fedagg_norms_batched",
        interpret=interpret,
    )(shaped1(x_t), shapedb(x_stales), shapedb(deltas))
    return _sum_gram(*out)


def _apply_rows(etas_ref, xt_ref, delta, b: int, out_ref):
    """out = x_t + sum_i etas[i] * delta(i) on one tile, ``delta(i)`` the
    f32 (rows, LANES) tile of delta i. Accumulated on the VPU in arrival
    order — the exact f32 arithmetic of B sequential AXPYs, where an MXU
    ``etas @ deltas`` would round every delta to bf16."""
    acc = _f32(xt_ref[...])
    for i in range(b):
        acc = acc + etas_ref[0, i] * delta(i)
    out_ref[...] = acc.astype(out_ref.dtype)


def _apply_batched_kernel(etas_ref, xt_ref, d_ref, out_ref):
    _apply_rows(etas_ref, xt_ref, lambda i: _f32(d_ref[i]), d_ref.shape[0],
                out_ref)


def fedagg_apply_batched(x_t: jax.Array, deltas: jax.Array, etas: jax.Array,
                         *, interpret: Optional[bool] = None) -> jax.Array:
    """Batched Eq.(5): x_t + sum_b etas[b] * deltas[b] in ONE grid sweep.

    With etas from ``sequential_batch_schedule`` this equals applying the B
    updates one at a time (Eq.(5) is linear in the deltas), while reading
    x_t once instead of B times and writing one output instead of B.
    """
    interpret = resolve_interpret(interpret)
    b, n = deltas.shape
    assert x_t.shape == (n,) and etas.shape == (b,)
    rows = _batched_rows(b, n, interpret, deltas.dtype.itemsize)
    block = rows * LANES
    assert n % (BLOCK_ROWS * LANES) == 0, (n, BLOCK_ROWS * LANES)
    g = n // block
    out = pl.pallas_call(
        _apply_batched_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, b), lambda i: (0, 0)),          # etas broadcast
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((b, rows, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g * rows, LANES), x_t.dtype),
        compiler_params=_batched_params(interpret),
        name="fedagg_apply_batched",
        interpret=interpret,
    )(etas.reshape(1, b).astype(jnp.float32),
      x_t.reshape(g * rows, LANES), deltas.reshape(b, g * rows, LANES))
    return out.reshape(n)


def _fused_kernel(scal_ref, xt_ref, xs_ref, d_ref, out_ref, dist_ref,
                  dn_ref):
    """Beyond-paper single-phase variant for the displacement-GMIS server:
    dist is known a-priori (see DESIGN.md §3), so gamma/eta are computed on
    the host and the whole aggregation is ONE pass: read (x_t, delta),
    write x_{t+1}, and opportunistically emit the partial norms needed for
    the *next* gamma bookkeeping."""
    eta = scal_ref[0, 0]
    xt = _f32(xt_ref[...])
    d = _f32(d_ref[...])
    out_ref[...] = (xt + eta * d).astype(out_ref.dtype)
    diff = xt - _f32(xs_ref[...])
    dist_ref[...] = _fold(diff * diff)
    dn_ref[...] = _fold(d * d)


def fedagg_fused(x_t: jax.Array, x_stale: jax.Array, delta: jax.Array,
                 eta: jax.Array, *, interpret: Optional[bool] = None):
    """One-pass: returns (x_t + eta*delta, (dist^2, ||delta||^2) partials
    summed). Used when eta is precomputed (displacement mode) but the norms
    are still wanted for telemetry/controller."""
    n = x_t.shape[0]
    block = BLOCK_ROWS * LANES
    assert n % block == 0, (n, block)
    g = n // block
    shaped = lambda a: a.reshape(g * BLOCK_ROWS, LANES)
    spec, shape = _partials(g)
    out, dist, dn = pl.pallas_call(
        _fused_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            spec, spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g * BLOCK_ROWS, LANES), x_t.dtype),
            shape, shape,
        ],
        name="fedagg_fused",
        interpret=resolve_interpret(interpret),
    )(eta.reshape(1, 1).astype(jnp.float32), shaped(x_t), shaped(x_stale),
      shaped(delta))
    return out.reshape(n), jnp.stack([jnp.sum(dist), jnp.sum(dn)])


# ------------------------------------------------- quantization-fused path --
# Compressed delta transport (DESIGN.md §13): deltas arrive as per-block-
# scaled int8 (one f32 scale per QBLOCK elements, repro.core.compression)
# and are dequantized INSIDE the grid step — one upcast + one broadcast
# multiply per scale block — so the f32 delta vector is never
# materialized in HBM. bf16 deltas need none of this: the f32 kernels
# above upcast tiles on load, so bf16 rides them unchanged.
#
# Scale layout: each grid step reads its tile's scales as one lane-major
# row, (1, spb) for a single delta and (B, spb) for a batch, from a
# (steps, 1 or B, spb) array whose block spans the last two dims.

def _scaled(v: jax.Array, s_row: jax.Array) -> jax.Array:
    """Multiply each QBLOCK_ROWS-row block of the f32 tile ``v`` (rows,
    LANES) by its scale; ``s_row`` (1, spb) holds one scale per block.
    The row turns into a column by a small transpose, never by a reshape
    that moves lanes into sublanes."""
    spb = s_row.shape[1]
    col = jnp.transpose(s_row)[:, :, None]             # (spb, 1, 1)
    return (v.reshape(spb, QBLOCK_ROWS, LANES) * col).reshape(v.shape)


def _dequant_tile(q, s):
    """Dequantize one VMEM tile. ``q`` int8 (rows, LANES) with ``s`` (1,
    spb), or (B, rows, LANES) with ``s`` (B, spb). Returns the f32
    tile(s)."""
    v = q.astype(jnp.float32)
    if q.ndim == 2:
        return _scaled(v, s)
    return jnp.stack([_scaled(v[i], s[i:i + 1]) for i in range(q.shape[0])])


def _scale_rows(scales: jax.Array, g: int) -> jax.Array:
    """(n // QBLOCK,) scales -> (g, 1, spb); (B, n // QBLOCK) -> (g, B,
    spb): grid step i reads row block i."""
    if scales.ndim == 1:
        return scales.reshape(g, 1, -1)
    return scales.reshape(scales.shape[0], g, -1).transpose(1, 0, 2)


def _norms_q_kernel(xt_ref, xs_ref, q_ref, s_ref, dist_ref, dn_ref):
    diff = _f32(xt_ref[...]) - _f32(xs_ref[...])
    d = _dequant_tile(q_ref[...], s_ref[0])
    dist_ref[...] = _fold(diff * diff)
    dn_ref[...] = _fold(d * d)


def fedagg_norms_q(x_t: jax.Array, x_stale: jax.Array, q: jax.Array,
                   scales: jax.Array, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Quant-fused phase 1: like :func:`fedagg_norms` but the delta arrives
    as int8 ``q`` (n,) + f32 ``scales`` (n // QBLOCK,). The emitted
    ||delta||^2 is the DEQUANTIZED norm — exactly what the AXPY applies, so
    screening/gamma computed from it see the transported values."""
    n = x_t.shape[0]
    block = BLOCK_ROWS * LANES
    assert n % block == 0, (n, block)
    assert q.shape == (n,) and scales.shape == (n // QBLOCK,), (
        q.shape, scales.shape, n)
    g = n // block
    spb = BLOCK_ROWS // QBLOCK_ROWS
    shaped = lambda a: a.reshape(g * BLOCK_ROWS, LANES)
    spec, shape = _partials(g)
    dist, dn = pl.pallas_call(
        _norms_q_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, spb), lambda i: (i, 0, 0)),
        ],
        out_specs=[spec, spec],
        out_shape=[shape, shape],
        name="fedagg_norms_q",
        interpret=resolve_interpret(interpret),
    )(shaped(x_t), shaped(x_stale), shaped(q), _scale_rows(scales, g))
    return jnp.stack([jnp.sum(dist), jnp.sum(dn)])


def _axpy_q_kernel(eta_ref, xt_ref, q_ref, s_ref, out_ref):
    eta = eta_ref[0, 0]
    d = _dequant_tile(q_ref[...], s_ref[0])
    out_ref[...] = (xt_ref[...].astype(jnp.float32) + eta * d
                    ).astype(out_ref.dtype)


def fedagg_axpy_q(x_t: jax.Array, q: jax.Array, scales: jax.Array,
                  eta: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Quant-fused Eq.(5): x_t + eta * dequant(q, scales), one sweep."""
    n = x_t.shape[0]
    block = BLOCK_ROWS * LANES
    assert n % block == 0, (n, block)
    assert q.shape == (n,) and scales.shape == (n // QBLOCK,)
    g = n // block
    spb = BLOCK_ROWS // QBLOCK_ROWS
    shaped = lambda a: a.reshape(g * BLOCK_ROWS, LANES)
    out = pl.pallas_call(
        _axpy_q_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),          # eta broadcast
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, spb), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g * BLOCK_ROWS, LANES), x_t.dtype),
        name="fedagg_axpy_q",
        interpret=resolve_interpret(interpret),
    )(eta.reshape(1, 1).astype(jnp.float32), shaped(x_t), shaped(q),
      _scale_rows(scales, g))
    return out.reshape(n)


def _norms_batched_q_kernel(xt_ref, xs_ref, q_ref, s_ref, *out_refs):
    _gram_partials(_f32(xt_ref[...]), _f32(xs_ref[...]),
                   _dequant_tile(q_ref[...], s_ref[0]), *out_refs)


def fedagg_norms_batched_q(x_t: jax.Array, x_stales: jax.Array,
                           qs: jax.Array, scales: jax.Array, *,
                           interpret: Optional[bool] = None):
    """Batched phase 1 over B quantized arrivals: like
    :func:`fedagg_norms_batched` with ``qs`` (B, n) int8 + ``scales``
    (B, n // QBLOCK) f32 resident instead of f32 deltas — the delta tiles
    cost 1 byte/element, so the free-batch knee moves from 15 to 24
    (``batched_b_max(1)``). All four outputs are computed on the
    dequantized values."""
    interpret = resolve_interpret(interpret)
    b, n = qs.shape
    assert x_t.shape == (n,) and x_stales.shape == (b, n)
    assert scales.shape == (b, n // QBLOCK), (scales.shape, b, n // QBLOCK)
    rows = _batched_rows(b, n, interpret, 1)
    block = rows * LANES
    assert n % (BLOCK_ROWS * LANES) == 0, (n, BLOCK_ROWS * LANES)
    g = n // block
    spb = rows // QBLOCK_ROWS
    shaped1 = lambda a: a.reshape(g * rows, LANES)
    shapedb = lambda a: a.reshape(b, g * rows, LANES)
    specs, shapes = _gram_outputs(g, b)
    out = pl.pallas_call(
        _norms_batched_q_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((b, rows, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((b, rows, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((1, b, spb), lambda i: (i, 0, 0)),
        ],
        out_specs=specs,
        out_shape=shapes,
        compiler_params=_batched_params(interpret),
        name="fedagg_norms_batched_q",
        interpret=interpret,
    )(shaped1(x_t), shapedb(x_stales), shapedb(qs), _scale_rows(scales, g))
    return _sum_gram(*out)


def _apply_batched_q_kernel(etas_ref, xt_ref, q_ref, s_ref, out_ref):
    scales = s_ref[0]                               # (B, spb)
    _apply_rows(etas_ref, xt_ref,
                lambda i: _scaled(q_ref[i].astype(jnp.float32),
                                  scales[i:i + 1]),
                q_ref.shape[0], out_ref)


def fedagg_apply_batched_q(x_t: jax.Array, qs: jax.Array, scales: jax.Array,
                           etas: jax.Array, *,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Batched quant-fused Eq.(5): x_t + sum_b etas[b] * dequant(qs[b])."""
    interpret = resolve_interpret(interpret)
    b, n = qs.shape
    assert x_t.shape == (n,) and etas.shape == (b,)
    assert scales.shape == (b, n // QBLOCK)
    rows = _batched_rows(b, n, interpret, 1)
    block = rows * LANES
    assert n % (BLOCK_ROWS * LANES) == 0, (n, BLOCK_ROWS * LANES)
    g = n // block
    spb = rows // QBLOCK_ROWS
    out = pl.pallas_call(
        _apply_batched_q_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, b), lambda i: (0, 0)),          # etas broadcast
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((b, rows, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((1, b, spb), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g * rows, LANES), x_t.dtype),
        compiler_params=_batched_params(interpret),
        name="fedagg_apply_batched_q",
        interpret=interpret,
    )(etas.reshape(1, b).astype(jnp.float32),
      x_t.reshape(g * rows, LANES), qs.reshape(b, g * rows, LANES),
      _scale_rows(scales, g))
    return out.reshape(n)
