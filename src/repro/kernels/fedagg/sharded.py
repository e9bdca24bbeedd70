"""Model-sharded fedagg entry points (DESIGN.md §14).

Eq. (5-7) is elementwise ops plus Euclidean norms over one padded flat
vector, so it shards along a ``model`` axis with exactly ONE collective
per aggregation: the squared-norm partials. Each shard runs the
unchanged Pallas grid (`fedagg.py`) over its contiguous slice — the
server pads with ``block = kernel BLOCK * shards`` so every shard is a
whole number of kernel blocks — and a single ``psum`` over the mesh's
``model`` axis turns per-shard partial sums into the global
``||x_t - x_stale||^2`` and ``||delta||^2``. gamma and eta are then
computed replicated inside the same dispatch (Eq. 6-7 are scalar
functions of the psum'd norms, so every shard derives the identical
scalars) and the Eq. 5 AXPY applies shard-locally with no further
communication. The batched Gram sweep is the same shape: all four
outputs (dist0/dn/cross/gram) are contractions over the vector axis,
so one psum of the ``(B,)``/``(B, B)`` partials reproduces the
replicated sweep, and the host-side sequential-equivalence schedule
(`aggregation.sequential_batch_schedule`) runs on the psum'd values
unchanged.

Numerics: per-shard summation + psum reorders the float reduction
versus the replicated single-grid sweep, so results match to float
tolerance (observed ~2e-5 relative), not bit-exactly — the same class
of difference the cohort engines pin with rtol=2e-5.

``check_vma=False`` on every ``jax.shard_map`` is load-bearing: a
``pallas_call`` has no varying-manual-axes rule, so the checker rejects
the body otherwise.

Entry points mirror `ops.py` signatures plus a ``shards`` kwarg; all
dispatches are cached per (shards, scalars, kernel mode) so the server
traces once per shape. The kernel mode is resolved from the platform when
the call is made (``fedagg.resolve_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core.aggregation import (gamma_eta_from_sq,
                                    sequential_batch_schedule)
from repro.kernels.fedagg import fedagg
from repro.launch import mesh as mesh_lib
from repro.sharding.specs import (FLAT_SCALES_SPEC, FLAT_STACKED_SCALES_SPEC,
                                  FLAT_STACKED_SPEC, FLAT_VEC_SPEC,
                                  flat_sharding)

#: replicated operands/outputs (scalars, eta rows) on the (pod, model) mesh
_REP = PartitionSpec()
_mode = fedagg.resolve_interpret


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


# ------------------------------------------------------- single-update --

@functools.lru_cache(maxsize=None)
def _aggregate(shards, lam, eps, cap, interpret):
    def body(x_t, x_stale, delta):
        part = fedagg.fedagg_norms(x_t, x_stale, delta, interpret=interpret)
        sq = jax.lax.psum(part, "model")
        gamma, eta, dist, dnorm = gamma_eta_from_sq(sq[0], sq[1],
                                                    lam, eps, cap)
        new = fedagg.fedagg_axpy(x_t, delta, eta, interpret=interpret)
        return new, gamma, eta, dist, dnorm

    return _smap(body, shards, (FLAT_VEC_SPEC,) * 3,
                 (FLAT_VEC_SPEC, _REP, _REP, _REP, _REP))


def flat_aggregate(x_t, x_stale, delta, *, lam, eps, cap=0.0, shards,
                   interpret=None, in_place=False):
    """Sharded twin of ``ops.flat_aggregate``: one Eq.(5-7) dispatch, one
    cross-shard psum. Returns (new_vec [model-sharded], gamma, eta, dist,
    dnorm). It always writes a fresh vector: ``in_place`` must be False."""
    if in_place:
        raise ValueError("the sharded aggregation does not write in place")
    return _aggregate(int(shards), float(lam), float(eps), float(cap),
                      _mode(interpret))(x_t, x_stale, delta)


@functools.lru_cache(maxsize=None)
def _aggregate_displacement(shards, lam, eps, cap, interpret):
    def body(x_t, disp, delta, zeros):
        part = fedagg.fedagg_norms(disp, zeros, delta, interpret=interpret)
        sq = jax.lax.psum(part, "model")
        gamma, eta, dist, dnorm = gamma_eta_from_sq(sq[0], sq[1],
                                                    lam, eps, cap)
        new = fedagg.fedagg_axpy(x_t, delta, eta, interpret=interpret)
        return new, gamma, eta, dist, dnorm

    return _smap(body, shards, (FLAT_VEC_SPEC,) * 4,
                 (FLAT_VEC_SPEC, _REP, _REP, _REP, _REP))


def flat_aggregate_displacement(x_t, disp, delta, zeros, *, lam, eps,
                                cap=0.0, shards, interpret=None):
    """Sharded twin of ``ops.flat_aggregate_displacement``."""
    return _aggregate_displacement(int(shards), float(lam), float(eps),
                                   float(cap), _mode(interpret))(
        x_t, disp, delta, zeros)


@functools.lru_cache(maxsize=None)
def _aggregate_q(shards, lam, eps, cap, interpret):
    def body(x_t, x_stale, q, scales):
        part = fedagg.fedagg_norms_q(x_t, x_stale, q, scales,
                                     interpret=interpret)
        sq = jax.lax.psum(part, "model")
        gamma, eta, dist, dnorm = gamma_eta_from_sq(sq[0], sq[1],
                                                    lam, eps, cap)
        new = fedagg.fedagg_axpy_q(x_t, q, scales, eta, interpret=interpret)
        return new, gamma, eta, dist, dnorm

    # QBLOCK divides the kernel BLOCK, which divides the per-shard
    # length, so a contiguous `model` split of the scale vector keeps
    # every scale next to the q block it dequantizes (specs.py).
    return _smap(body, shards,
                 (FLAT_VEC_SPEC, FLAT_VEC_SPEC, FLAT_VEC_SPEC,
                  FLAT_SCALES_SPEC),
                 (FLAT_VEC_SPEC, _REP, _REP, _REP, _REP))


def flat_aggregate_q(x_t, x_stale, q, scales, *, lam, eps, cap=0.0,
                     shards, interpret=None):
    """Sharded twin of ``ops.flat_aggregate_q``: the int8 payload is
    dequantized per grid tile inside each shard, norms psum once."""
    return _aggregate_q(int(shards), float(lam), float(eps), float(cap),
                        _mode(interpret))(x_t, x_stale, q, scales)


@functools.lru_cache(maxsize=None)
def _aggregate_displacement_q(shards, lam, eps, cap, interpret):
    def body(x_t, disp, q, scales, zeros):
        part = fedagg.fedagg_norms_q(disp, zeros, q, scales,
                                     interpret=interpret)
        sq = jax.lax.psum(part, "model")
        gamma, eta, dist, dnorm = gamma_eta_from_sq(sq[0], sq[1],
                                                    lam, eps, cap)
        new = fedagg.fedagg_axpy_q(x_t, q, scales, eta, interpret=interpret)
        return new, gamma, eta, dist, dnorm

    return _smap(body, shards,
                 (FLAT_VEC_SPEC, FLAT_VEC_SPEC, FLAT_VEC_SPEC,
                  FLAT_SCALES_SPEC, FLAT_VEC_SPEC),
                 (FLAT_VEC_SPEC, _REP, _REP, _REP, _REP))


def flat_aggregate_displacement_q(x_t, disp, q, scales, zeros, *, lam, eps,
                                  cap=0.0, shards, interpret=None):
    """Sharded twin of ``ops.flat_aggregate_displacement_q``."""
    return _aggregate_displacement_q(int(shards), float(lam), float(eps),
                                     float(cap), _mode(interpret))(
        x_t, disp, q, scales, zeros)


# ------------------------------------------------------------- batched --
# Two dispatches with the host-side sequential-equivalence schedule
# between them, exactly like ops.flat_aggregate_batched: the Gram sweep
# psums all four norm outputs (the only collective), the apply sweep is
# shard-local.

@functools.lru_cache(maxsize=None)
def _norms_batched(shards, interpret):
    def body(x_t, x_stales, deltas):
        part = fedagg.fedagg_norms_batched(x_t, x_stales, deltas,
                                           interpret=interpret)
        return jax.lax.psum(part, "model")

    return _smap(body, shards,
                 (FLAT_VEC_SPEC, FLAT_STACKED_SPEC, FLAT_STACKED_SPEC),
                 (_REP, _REP, _REP, _REP))


@functools.lru_cache(maxsize=None)
def _apply_batched(shards, interpret):
    def body(x_t, deltas, etas):
        return fedagg.fedagg_apply_batched(x_t, deltas, etas,
                                           interpret=interpret)

    return _smap(body, shards, (FLAT_VEC_SPEC, FLAT_STACKED_SPEC, _REP),
                 FLAT_VEC_SPEC)


def flat_aggregate_batched(x_t, x_stales, deltas, *, lam, eps, cap=0.0,
                           shards, interpret=None, screen=None):
    """Sharded twin of ``ops.flat_aggregate_batched``: B concurrent
    arrivals, one psum of the (B,)/(B,B) Gram partials, host schedule,
    shard-local apply. Same return signature (new_vec is model-sharded)."""
    d0, dn_sq, cross, gram = _norms_batched(int(shards), _mode(interpret))(
        x_t, x_stales, deltas)
    scales = None
    if screen is not None:
        dns = np.sqrt(np.maximum(np.asarray(dn_sq, np.float64), 0.0))
        scales = screen(dns.astype(np.float32))
    etas, gammas, dists, dnorms = sequential_batch_schedule(
        d0, dn_sq, cross, gram, lam=lam, eps=eps, cap=cap, scales=scales)
    new = _apply_batched(int(shards), _mode(interpret))(
        x_t, deltas, jnp.asarray(etas))
    return new, etas, gammas, dists, dnorms, scales


@functools.lru_cache(maxsize=None)
def _norms_batched_q(shards, interpret):
    def body(x_t, x_stales, qs, qscales):
        part = fedagg.fedagg_norms_batched_q(x_t, x_stales, qs, qscales,
                                             interpret=interpret)
        return jax.lax.psum(part, "model")

    return _smap(body, shards,
                 (FLAT_VEC_SPEC, FLAT_STACKED_SPEC, FLAT_STACKED_SPEC,
                  FLAT_STACKED_SCALES_SPEC),
                 (_REP, _REP, _REP, _REP))


@functools.lru_cache(maxsize=None)
def _apply_batched_q(shards, interpret):
    def body(x_t, qs, qscales, etas):
        return fedagg.fedagg_apply_batched_q(x_t, qs, qscales, etas,
                                             interpret=interpret)

    return _smap(body, shards,
                 (FLAT_VEC_SPEC, FLAT_STACKED_SPEC,
                  FLAT_STACKED_SCALES_SPEC, _REP),
                 FLAT_VEC_SPEC)


def flat_aggregate_batched_q(x_t, x_stales, qs, qscales, *, lam, eps,
                             cap=0.0, shards, interpret=None, screen=None):
    """Sharded twin of ``ops.flat_aggregate_batched_q``: int8 rows
    dequantize per grid tile inside each shard; the screening decider
    sees the psum'd (global) dequantized norms."""
    d0, dn_sq, cross, gram = _norms_batched_q(int(shards), _mode(interpret))(
        x_t, x_stales, qs, qscales)
    scales = None
    if screen is not None:
        dns = np.sqrt(np.maximum(np.asarray(dn_sq, np.float64), 0.0))
        scales = screen(dns.astype(np.float32))
    etas, gammas, dists, dnorms = sequential_batch_schedule(
        d0, dn_sq, cross, gram, lam=lam, eps=eps, cap=cap, scales=scales)
    new = _apply_batched_q(int(shards), _mode(interpret))(
        x_t, qs, qscales, jnp.asarray(etas))
    return new, etas, gammas, dists, dnorms, scales
