"""Public wrappers over the fused fedagg Pallas kernels.

Two API levels:

* **flat** (``flat_aggregate`` / ``flat_aggregate_batched``) — operates on
  already-padded flat f32 vectors. This is the hot path of the flat-state
  server runtime (``AsyncFedEDServer(backend="pallas")``), which keeps the
  global model flattened permanently so no per-step tree walk happens.
* **pytree** (``asyncfeded_aggregate_pallas`` /
  ``asyncfeded_aggregate_batched_pallas``) — drop-in replacements for
  ``repro.core.aggregation.asyncfeded_aggregate`` that flatten/unflatten at
  the boundary. Used by tests and one-off callers.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import (AggregationResult, gamma_eta_from_sq,
                                    sequential_batch_schedule)
from repro.kernels.fedagg import fedagg
from repro.kernels.fedagg.fedagg import BLOCK_ROWS, LANES
from repro.utils import pytree as pt
from repro.utils import trace

PyTree = Any
_BLOCK = BLOCK_ROWS * LANES


def pad_flat_vector(vec: jax.Array) -> jax.Array:
    """Zero-pad a flat (n,) vector to the kernel BLOCK multiple. Zeros
    contribute 0 to every norm/dot the kernels emit and are sliced off
    after the AXPY, so padding is value-transparent."""
    pad = (-vec.shape[0]) % _BLOCK
    return jnp.pad(vec, (0, pad)) if pad else vec


def _pad_flat(tree: PyTree) -> jax.Array:
    return pad_flat_vector(pt.tree_flatten_to_vector(tree))


# ---------------------------------------------------------------- flat API --
# The flat entry points are jit-cached: the server calls them once per
# arrival with fixed shapes, so tracing/lowering the kernel grid happens
# once per (shape, batch) instead of per update. ``interpret=None`` (the
# default everywhere) lets the kernels pick their mode from the platform
# at trace time (``fedagg.resolve_interpret``).

@functools.lru_cache(maxsize=None)
def flat_aggregate_program(in_place: bool):
    """The jitted program behind :func:`flat_aggregate`, one per
    ``in_place``. In place, the jit donates ``delta`` and the AXPY writes
    x_{t+1} into its buffer, so the step allocates no model-sized output;
    the norms sweep has read ``delta`` by then, since the AXPY needs eta."""

    def flat_aggregate(x_t, x_stale, delta, *, lam, eps, cap, interpret):
        sq = fedagg.fedagg_norms(x_t, x_stale, delta, interpret=interpret)
        gamma, eta, dist, dnorm = gamma_eta_from_sq(sq[0], sq[1], lam, eps,
                                                    cap)
        new = fedagg.fedagg_axpy(x_t, delta, eta, interpret=interpret,
                                 in_place=in_place)
        return new, gamma, eta, dist, dnorm

    return jax.jit(flat_aggregate,
                   static_argnames=("lam", "eps", "cap", "interpret"),
                   donate_argnames=("delta",) if in_place else ())


def flat_aggregate(x_t: jax.Array, x_stale: jax.Array, delta: jax.Array, *,
                   lam: float, eps: float, cap: float = 0.0,
                   interpret: Optional[bool] = None, in_place: bool = False):
    """One Eq.(5-7) step on padded flat vectors: a norms sweep, scalar
    gamma/eta, an AXPY sweep. Returns (new_vec, gamma, eta, dist, dnorm).
    With ``in_place`` (an f32 ``delta`` the caller owns and reads no
    more) ``new_vec`` takes over ``delta``'s buffer and ``delta`` is
    deleted; otherwise every argument stays valid."""
    return flat_aggregate_program(bool(in_place))(
        x_t, x_stale, delta, lam=lam, eps=eps, cap=cap, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("lam", "eps", "cap", "interpret"))
def flat_aggregate_displacement(x_t: jax.Array, disp: jax.Array,
                                delta: jax.Array, zeros: jax.Array, *,
                                lam: float, eps: float, cap: float = 0.0,
                                interpret: Optional[bool] = None):
    """Displacement-GMIS variant (DESIGN.md §3): the stale model is never
    materialized; ``disp`` = x_t - x_{t-tau} is maintained incrementally, so
    one norms sweep over (disp, delta) — with a cached ``zeros`` vector in
    the x_stale slot — yields both Eq.(6) norms, then one AXPY sweep applies
    Eq.(5). Returns (new_vec, gamma, eta, dist, dnorm)."""
    sq = fedagg.fedagg_norms(disp, zeros, delta, interpret=interpret)
    gamma, eta, dist, dnorm = gamma_eta_from_sq(sq[0], sq[1], lam, eps, cap)
    new = fedagg.fedagg_axpy(x_t, delta, eta, interpret=interpret)
    return new, gamma, eta, dist, dnorm


_norms_batched = jax.jit(fedagg.fedagg_norms_batched,
                         static_argnames=("interpret",))

_apply_batched = jax.jit(fedagg.fedagg_apply_batched,
                         static_argnames=("interpret",))


def _host_schedule(d0, dn_sq, cross, gram, *, lam: float, eps: float,
                   cap: float, screen):
    """Read the Gram sweep's outputs to the host and resolve the
    sequential-equivalence schedule there (screening folded in). Returns
    (etas, gammas, dists, dnorms, scales)."""
    with trace.span("server.sync", reads=4):
        d0, dn_sq, cross, gram = (np.asarray(a)
                                  for a in (d0, dn_sq, cross, gram))
    scales = None
    if screen is not None:
        dns = np.sqrt(np.maximum(np.asarray(dn_sq, np.float64), 0.0))
        scales = screen(dns.astype(np.float32))
    with trace.span("server.schedule"):
        etas, gammas, dists, dnorms = sequential_batch_schedule(
            d0, dn_sq, cross, gram, lam=lam, eps=eps, cap=cap,
            scales=scales)
    return etas, gammas, dists, dnorms, scales


def flat_aggregate_batched(x_t: jax.Array, x_stales: jax.Array,
                           deltas: jax.Array, *, lam: float, eps: float,
                           cap: float = 0.0, interpret: Optional[bool] = None,
                           screen=None):
    """B concurrent arrivals in two grid sweeps, sequential-equivalent to B
    one-at-a-time ``flat_aggregate`` calls (see
    ``aggregation.sequential_batch_schedule``).

    x_t (n,), x_stales (B, n), deltas (B, n), n a BLOCK multiple.
    Returns (new_vec, etas, gammas, dists, dnorms, scales) — the per-update
    scalars as f32 numpy arrays in arrival order; etas are the effective
    multipliers on the raw deltas. Not jitted end-to-end: the
    sequential-equivalence schedule resolves on the host between sweeps.

    ``screen`` (optional) is a norm-screening decider — typically
    ``NormScreen.decide_batch`` — called with the kernel-emitted raw delta
    norms in arrival order; it returns per-update scale factors (1 accept,
    (0,1) clip, 0 reject) folded into the schedule. This is where the
    defense reuses the batched Gram sweep: no extra pass over the
    parameter vector happens. ``scales`` is None when ``screen`` is.
    """
    etas, gammas, dists, dnorms, scales = _host_schedule(
        *_norms_batched(x_t, x_stales, deltas, interpret=interpret),
        lam=lam, eps=eps, cap=cap, screen=screen)
    new = _apply_batched(x_t, deltas, jnp.asarray(etas),
                         interpret=interpret)
    return new, etas, gammas, dists, dnorms, scales


# --------------------------------------------------- quant-fused flat API --
# Compressed-transport twins of the flat entry points (DESIGN.md §13): the
# delta arrives as per-block-scaled int8 (q (n,), scales (n // QBLOCK,))
# and is dequantized inside the grid sweeps, never materialized as f32 in
# HBM. bf16 deltas don't need these — the f32 kernels upcast tiles on
# load, so bf16 payloads ride the uncompressed entry points unchanged.

@functools.partial(jax.jit, static_argnames=("lam", "eps", "cap", "interpret"))
def flat_aggregate_q(x_t: jax.Array, x_stale: jax.Array, q: jax.Array,
                     scales: jax.Array, *, lam: float, eps: float,
                     cap: float = 0.0, interpret: Optional[bool] = None):
    """Quant-fused Eq.(5-7) step. The emitted dnorm is the dequantized
    delta norm — exactly what the AXPY applies."""
    sq = fedagg.fedagg_norms_q(x_t, x_stale, q, scales, interpret=interpret)
    gamma, eta, dist, dnorm = gamma_eta_from_sq(sq[0], sq[1], lam, eps, cap)
    new = fedagg.fedagg_axpy_q(x_t, q, scales, eta, interpret=interpret)
    return new, gamma, eta, dist, dnorm


@functools.partial(jax.jit, static_argnames=("lam", "eps", "cap", "interpret"))
def flat_aggregate_displacement_q(x_t: jax.Array, disp: jax.Array,
                                  q: jax.Array, scales: jax.Array,
                                  zeros: jax.Array, *, lam: float, eps: float,
                                  cap: float = 0.0,
                                  interpret: Optional[bool] = None):
    """Displacement-GMIS variant of :func:`flat_aggregate_q`."""
    sq = fedagg.fedagg_norms_q(disp, zeros, q, scales, interpret=interpret)
    gamma, eta, dist, dnorm = gamma_eta_from_sq(sq[0], sq[1], lam, eps, cap)
    new = fedagg.fedagg_axpy_q(x_t, q, scales, eta, interpret=interpret)
    return new, gamma, eta, dist, dnorm


_norms_batched_q = jax.jit(fedagg.fedagg_norms_batched_q,
                           static_argnames=("interpret",))
_apply_batched_q = jax.jit(fedagg.fedagg_apply_batched_q,
                           static_argnames=("interpret",))


def flat_aggregate_batched_q(x_t: jax.Array, x_stales: jax.Array,
                             qs: jax.Array, qscales: jax.Array, *,
                             lam: float, eps: float, cap: float = 0.0,
                             interpret: Optional[bool] = None, screen=None):
    """Quant-fused twin of :func:`flat_aggregate_batched`: B int8 arrivals
    (qs (B, n) + qscales (B, n // QBLOCK)) drained in two grid sweeps.
    The screening decider sees the kernel-emitted DEQUANTIZED norms, and
    clip scales fold into the eta schedule exactly (int8 clip-by-scales is
    exact). Same return signature as the uncompressed path."""
    etas, gammas, dists, dnorms, scales = _host_schedule(
        *_norms_batched_q(x_t, x_stales, qs, qscales, interpret=interpret),
        lam=lam, eps=eps, cap=cap, screen=screen)
    new = _apply_batched_q(x_t, qs, qscales, jnp.asarray(etas),
                           interpret=interpret)
    return new, etas, gammas, dists, dnorms, scales


# -------------------------------------------------------------- pytree API --

@functools.partial(jax.jit, static_argnames=("lam", "eps", "cap", "interpret"))
def asyncfeded_aggregate_pallas(x_t: PyTree, x_stale: PyTree, delta: PyTree,
                                *, lam: float, eps: float, cap: float = 0.0,
                                interpret: Optional[bool] = None
                                ) -> AggregationResult:
    xt = _pad_flat(x_t)
    xs = _pad_flat(x_stale)
    d = _pad_flat(delta)
    new_flat, gamma, eta, dist, dnorm = flat_aggregate(
        xt, xs, d, lam=lam, eps=eps, cap=cap, interpret=interpret)
    n = pt.tree_size(x_t)
    new = pt.tree_unflatten_from_vector(new_flat[:n], x_t)
    return AggregationResult(new, gamma, eta, dist, dnorm)


def asyncfeded_aggregate_batched_pallas(
        x_t: PyTree, x_stales: Sequence[PyTree], deltas: Sequence[PyTree], *,
        lam: float, eps: float, cap: float = 0.0,
        interpret: Optional[bool] = None) -> Tuple[PyTree, Any, Any, Any, Any]:
    """Batched pytree entry point: stacks B (stale, delta) pairs and drains
    them through the multi-delta kernels. Returns
    (new_params, etas, gammas, dists, dnorms). Not jitted — the
    sequential-equivalence schedule runs on the host between the sweeps."""
    spec = pt.FlatSpec(x_t, block=_BLOCK)
    xt = spec.flatten(x_t)
    xs = jnp.stack([spec.flatten(t) for t in x_stales])
    d = jnp.stack([spec.flatten(t) for t in deltas])
    new_flat, etas, gammas, dists, dnorms, _ = flat_aggregate_batched(
        xt, xs, d, lam=lam, eps=eps, cap=cap, interpret=interpret)
    return spec.unflatten(new_flat), etas, gammas, dists, dnorms
