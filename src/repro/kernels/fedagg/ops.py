"""The flat entry points over the fused fedagg Pallas kernels.

``flat_aggregate`` (one arrival) and ``flat_aggregate_batched`` (a burst)
operate on already-padded flat vectors. They are the hot path of the
flat-state server runtime (``AsyncFedEDServer(backend="pallas")``), which
keeps the global model flattened permanently so no per-step tree walk
happens. Every mode goes through them: ring or displacement staleness
(``drift``), and f32, bf16 or int8 payloads (``scales``, decoded inside
the kernels). ``sharded.py`` builds its entry points from the same step
and drain, with the norm reduction a ``psum``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import (gamma_eta_from_sq,
                                    sequential_batch_schedule)
from repro.kernels.fedagg import fedagg
from repro.kernels.fedagg.fedagg import BLOCK_ROWS, LANES
from repro.utils import trace

_BLOCK = BLOCK_ROWS * LANES


def pad_flat_vector(vec: jax.Array) -> jax.Array:
    """Zero-pad a flat (n,) vector to the kernel BLOCK multiple. Zeros
    contribute 0 to every norm/dot the kernels emit and are sliced off
    after the AXPY, so padding is value-transparent."""
    pad = (-vec.shape[0]) % _BLOCK
    return jnp.pad(vec, (0, pad)) if pad else vec


def _aggregate_step(x_t, x_stale, delta, scales, drift, *, lam, eps, cap,
                   interpret, in_place=False, reduce=lambda sq: sq):
    """One Eq.(5-7) step: a norms sweep over (x_t or ``drift``, x_stale,
    delta), ``reduce`` over its partial sums (a cross-shard ``psum`` under
    a mesh), scalar gamma/eta, an AXPY sweep. Returns (new_vec, scalars):
    ``scalars`` is f32 (gamma, eta, dist, dnorm) in one (4,) array, so the
    host reads them in one transfer."""
    sq = reduce(fedagg.fedagg_norms(x_t if drift is None else drift,
                                    x_stale, delta, scales,
                                    interpret=interpret))
    gamma, eta, dist, dnorm = gamma_eta_from_sq(sq[0], sq[1], lam, eps, cap)
    new = fedagg.fedagg_axpy(x_t, delta, eta, scales, interpret=interpret,
                             in_place=in_place)
    return new, jnp.stack([gamma, eta, dist, dnorm])


# The entry points are jit-cached: the server calls them once per arrival
# with fixed shapes, so tracing/lowering the kernel grid happens once per
# (shape, payload, batch) instead of per update. The kernels pick their
# mode from the platform at trace time (``fedagg.resolve_interpret``);
# the programs take ``interpret`` only so a described TPU can be compiled
# for from a CPU host.

@functools.lru_cache(maxsize=None)
def flat_aggregate_program(in_place: bool):
    """The jitted program behind :func:`flat_aggregate`, one per
    ``in_place``. In place, the jit donates ``delta`` and the AXPY writes
    x_{t+1} into its buffer, so the step allocates no model-sized output;
    the norms sweep has read ``delta`` by then, since the AXPY needs eta."""

    def flat_aggregate(x_t, x_stale, delta, scales=None, drift=None, *,
                       lam, eps, cap, interpret):
        return _aggregate_step(x_t, x_stale, delta, scales, drift, lam=lam,
                              eps=eps, cap=cap, interpret=interpret,
                              in_place=in_place)

    return jax.jit(flat_aggregate,
                   static_argnames=("lam", "eps", "cap", "interpret"),
                   donate_argnames=("delta",) if in_place else ())


def flat_aggregate(x_t: jax.Array, x_stale: jax.Array, delta: jax.Array, *,
                   scales=None, drift=None, lam: float, eps: float,
                   cap: float = 0.0, in_place: bool = False):
    """One Eq.(5-7) step on padded flat vectors. Returns (new_vec,
    scalars), ``scalars`` the f32 (4,) array (gamma, eta, dist, dnorm);
    dnorm is the norm of the delta the AXPY applies.

    ``delta`` is f32 or bf16, or int8 with its block ``scales``. Eq. 6's
    distance is ||x_t - x_stale||; displacement staleness (DESIGN.md §3)
    passes its maintained ``drift`` = x_t - x_{t-tau} with a zero
    ``x_stale``, so the stale model is never materialized. With
    ``in_place`` (an f32 ``delta`` the caller owns and reads no more)
    ``new_vec`` takes over ``delta``'s buffer and ``delta`` is deleted;
    otherwise every argument stays valid."""
    return flat_aggregate_program(bool(in_place))(
        x_t, x_stale, delta, scales, drift, lam=lam, eps=eps, cap=cap,
        interpret=None)


def pack_gram(d0, dn_sq, cross, gram):
    """The Gram sweep's outputs d0 (B,), dn_sq (B,), cross (B, B) and
    gram (B, B) as one f32 vector of length 2B + 2B², in that order, so
    the host reads them in one transfer."""
    return jnp.concatenate([d0, dn_sq, cross.ravel(), gram.ravel()])


def unpack_gram(packed: np.ndarray, b: int):
    """:func:`pack_gram`'s four arrays as views of its host copy."""
    bb = b * b
    return (packed[:b], packed[b:2 * b],
            packed[2 * b:2 * b + bb].reshape(b, b),
            packed[2 * b + bb:].reshape(b, b))


def _gram_sweep(x_t, x_stales, deltas, scales=None, *, interpret=None):
    return pack_gram(*fedagg.fedagg_norms_batched(
        x_t, x_stales, deltas, scales, interpret=interpret))


_norms_batched = jax.jit(_gram_sweep, static_argnames=("interpret",))

_apply_batched = jax.jit(fedagg.fedagg_apply_batched,
                         static_argnames=("interpret",))


def _host_schedule(packed, b: int, *, lam: float, eps: float, cap: float,
                   screen):
    """Read the packed Gram sweep (:func:`pack_gram`) to the host in one
    transfer and resolve the sequential-equivalence schedule there
    (screening folded in). Returns (etas, gammas, dists, dnorms, keep)."""
    with trace.span("server.sync", reads=1, d2h_bytes=packed.nbytes):
        d0, dn_sq, cross, gram = unpack_gram(np.asarray(packed), b)
    keep = None
    if screen is not None:
        dns = np.sqrt(np.maximum(np.asarray(dn_sq, np.float64), 0.0))
        keep = screen(dns.astype(np.float32))
    with trace.span("server.schedule"):
        etas, gammas, dists, dnorms = sequential_batch_schedule(
            d0, dn_sq, cross, gram, lam=lam, eps=eps, cap=cap,
            scales=keep)
    return etas, gammas, dists, dnorms, keep


def _batched_drain(norms, apply, x_t, x_stales, deltas, scales, *, lam, eps,
                  cap, screen):
    """The Gram sweep ``norms`` (packed by :func:`pack_gram`), the host
    schedule, the apply sweep ``apply``; both sweeps take the int8
    ``scales`` last where given."""
    payload = () if scales is None else (scales,)
    etas, gammas, dists, dnorms, keep = _host_schedule(
        norms(x_t, x_stales, deltas, *payload), deltas.shape[0],
        lam=lam, eps=eps, cap=cap, screen=screen)
    new = apply(x_t, deltas, jnp.asarray(etas), *payload)
    return new, etas, gammas, dists, dnorms, keep


def flat_aggregate_batched(x_t: jax.Array, x_stales: jax.Array,
                           deltas: jax.Array, *, scales=None, lam: float,
                           eps: float, cap: float = 0.0, screen=None):
    """B concurrent arrivals in two grid sweeps, sequential-equivalent to B
    one-at-a-time ``flat_aggregate`` calls (see
    ``aggregation.sequential_batch_schedule``).

    x_t (n,), x_stales (B, n), deltas (B, n), n a BLOCK multiple; int8
    deltas come with ``scales`` (B, n // QBLOCK).
    Returns (new_vec, etas, gammas, dists, dnorms, keep) — the per-update
    scalars as f32 numpy arrays in arrival order; etas are the effective
    multipliers on the raw deltas. Not jitted end-to-end: the
    sequential-equivalence schedule resolves on the host between sweeps.

    ``screen`` (optional) is a norm-screening decider — typically
    ``NormScreen.decide_batch`` — called with the kernel-emitted raw
    (dequantized) delta norms in arrival order; it returns per-update
    scale factors ``keep`` (1 accept, (0,1) clip, 0 reject) folded into
    the schedule. This is where the defense reuses the batched Gram
    sweep: no extra pass over the parameter vector happens. ``keep`` is
    None when ``screen`` is.
    """
    return _batched_drain(_norms_batched, _apply_batched, x_t, x_stales,
                         deltas, scales, lam=lam, eps=eps, cap=cap,
                         screen=screen)
