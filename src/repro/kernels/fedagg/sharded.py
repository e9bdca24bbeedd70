"""Model-sharded fedagg entry points (DESIGN.md §14).

Eq. (5-7) is elementwise ops plus Euclidean norms over one padded flat
vector, so it shards along a ``model`` axis with exactly ONE collective
per aggregation. The server pads to ``kernel BLOCK * shards``, so each
shard runs the unchanged Pallas grid over a whole number of kernel
blocks of its contiguous slice; a ``psum`` over ``model`` turns the
per-shard partial sums into the global norms (and, in the batched Gram
sweep, the global (B,)/(B, B) partials). gamma and eta are scalar
functions of those, so every shard derives them alike, and the Eq. 5
AXPY and the batched apply stay shard-local. An int8 payload's block
scales split with the vector: QBLOCK divides the kernel BLOCK, so each
scale stays next to the q block it dequantizes (specs.py).

The two entry points are ``ops``' step and drain inside a
``jax.shard_map``, with the norm reduction a ``psum``; their signatures
are ``ops``' plus ``shards``. Dispatches are cached per (shards, scalars,
kernel mode, payload), so the server traces once per shape.

Numerics: per-shard summation + psum reorders the float reduction
versus the replicated single-grid sweep, so results match to float
tolerance (observed ~2e-5 relative), not bit-exactly — the same class
of difference the cohort engines pin with rtol=2e-5.

``check_vma=False`` on every ``jax.shard_map`` is load-bearing: a
``pallas_call`` has no varying-manual-axes rule, so the checker rejects
the body otherwise.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec

from repro.kernels.fedagg import fedagg, ops
from repro.launch import mesh as mesh_lib
from repro.sharding.specs import (FLAT_SCALES_SPEC, FLAT_STACKED_SCALES_SPEC,
                                  FLAT_STACKED_SPEC, FLAT_VEC_SPEC,
                                  flat_sharding)

#: replicated operands/outputs (scalars, eta rows) on the (pod, model) mesh
_REP = PartitionSpec()


def _psum(partials):
    return jax.lax.psum(partials, "model")


@functools.lru_cache(maxsize=None)
def fedagg_mesh(shards: int):
    """The aggregation-side (pod=1, model=shards) mesh, cached per shard
    count (the device list is stable for the process lifetime)."""
    return mesh_lib.make_fedagg_mesh(int(shards))


def place_flat(vec: jax.Array, shards: int) -> jax.Array:
    """Commit a padded flat vector (or (B, n) stack) to its model-sharded
    layout. The length must be a multiple of ``kernel BLOCK * shards``."""
    return jax.device_put(
        vec, flat_sharding(fedagg_mesh(shards), stacked=vec.ndim == 2))


def _smap(body, shards, in_specs, out_specs):
    return jax.jit(jax.shard_map(body, mesh=fedagg_mesh(shards),
                                 in_specs=in_specs, out_specs=out_specs,
                                 check_vma=False))


@functools.lru_cache(maxsize=None)
def _aggregate(shards, lam, eps, cap, interpret, quant, displaced):
    """One Eq.(5-7) dispatch over (x_t, x_stale, delta[, scales][,
    drift])."""
    def body(x_t, x_stale, delta, *rest):
        scales = rest[0] if quant else None
        drift = rest[-1] if displaced else None
        return ops._aggregate_step(x_t, x_stale, delta, scales, drift,
                                  lam=lam, eps=eps, cap=cap,
                                  interpret=interpret, reduce=_psum)

    in_specs = ((FLAT_VEC_SPEC,) * 3 + (FLAT_SCALES_SPEC,) * quant
                + (FLAT_VEC_SPEC,) * displaced)
    return _smap(body, shards, in_specs,
                 (FLAT_VEC_SPEC, _REP, _REP, _REP, _REP))


def flat_aggregate(x_t, x_stale, delta, *, scales=None, drift=None, lam,
                   eps, cap=0.0, shards, in_place=False):
    """Sharded ``ops.flat_aggregate``: one Eq.(5-7) dispatch, one
    cross-shard psum. Returns (new_vec [model-sharded], gamma, eta, dist,
    dnorm). It always writes a fresh vector: ``in_place`` must be False."""
    if in_place:
        raise ValueError("the sharded aggregation does not write in place")
    fn = _aggregate(int(shards), float(lam), float(eps), float(cap),
                    fedagg.resolve_interpret(), scales is not None,
                    drift is not None)
    return fn(x_t, x_stale, delta,
              *(a for a in (scales, drift) if a is not None))


# Two dispatches with the host-side sequential-equivalence schedule
# between them, exactly like ops.flat_aggregate_batched: the Gram sweep
# psums all four norm outputs (the only collective), the apply sweep is
# shard-local.

@functools.lru_cache(maxsize=None)
def _norms_batched(shards, interpret, quant):
    def body(*args):
        return _psum(fedagg.fedagg_norms_batched(*args, interpret=interpret))

    return _smap(body, shards,
                 (FLAT_VEC_SPEC, FLAT_STACKED_SPEC, FLAT_STACKED_SPEC)
                 + (FLAT_STACKED_SCALES_SPEC,) * quant,
                 (_REP, _REP, _REP, _REP))


@functools.lru_cache(maxsize=None)
def _apply_batched(shards, interpret, quant):
    def body(*args):
        return fedagg.fedagg_apply_batched(*args, interpret=interpret)

    return _smap(body, shards,
                 (FLAT_VEC_SPEC, FLAT_STACKED_SPEC, _REP)
                 + (FLAT_STACKED_SCALES_SPEC,) * quant,
                 FLAT_VEC_SPEC)


def flat_aggregate_batched(x_t, x_stales, deltas, *, scales=None, lam, eps,
                           cap=0.0, shards, screen=None):
    """Sharded ``ops.flat_aggregate_batched``: B concurrent arrivals, one
    psum of the (B,)/(B,B) Gram partials, host schedule, shard-local
    apply. Same return signature (new_vec is model-sharded); the
    screening decider sees the psum'd (global) norms."""
    key = (int(shards), fedagg.resolve_interpret(), scales is not None)
    return ops._batched_drain(_norms_batched(*key), _apply_batched(*key),
                             x_t, x_stales, deltas, scales, lam=lam,
                             eps=eps, cap=cap, screen=screen)
