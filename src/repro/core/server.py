"""Server-side protocol implementations: AsyncFedED (Algorithm 1) and the
four baselines' aggregation rules (Appendix B.4).

Servers are pure protocol logic — no clocks, no sockets. The discrete-event
simulator (repro.core.simulator) drives them, under any client engine
(per-client loop, vectorized cohort, pod-sharded cohort — DESIGN.md §7-8):
by the time a ``ClientUpdate`` reaches ``on_update``/``round``, its delta
has already been gathered off whatever mesh trained it, so aggregation is
the one place where pod shards meet. The multi-pod launch path drives the
same classes with pod-sharded parameter pytrees.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig
from repro.core.adaptive_k import AdaptiveK
from repro.core.aggregation import (asyncfeded_aggregate,
                                    asyncfeded_aggregate_per_leaf,
                                    asyncfeded_aggregate_with_dist)
from repro.core.gmis import DisplacementGMIS, RingGMIS
from repro.core import compression, screening
from repro.kernels.fedagg import ops
from repro.utils import pytree as pt
from repro.utils import trace

PyTree = Any

#: the flat entry points, replicated (`kernels.fedagg.ops`) or
#: model-sharded (`kernels.fedagg.sharded`): the server binds one set per
#: instance so `_aggregate_flat`/`_drain_batched` never branch on the
#: shard count.
_AGG_OPS = ("flat_aggregate", "flat_aggregate_batched")


@dataclasses.dataclass
class ClientUpdate:
    client_id: int
    snapshot_iter: int
    k_used: int
    delta: PyTree
    num_samples: int = 1


@dataclasses.dataclass
class ServerReply:
    params: PyTree
    iteration: int
    k_next: int


@dataclasses.dataclass
class UpdateRecord:
    iteration: int
    client_id: int
    lag: int
    gamma: float
    eta: float
    k_used: int
    k_next: int
    dist: float
    delta_norm: float
    #: norm-screening verdict for this arrival (DESIGN.md §11): "accept"
    #: (also the value whenever screening is off), "clip" (delta scaled
    #: down to the k×EWMA threshold; ``eta`` is the effective multiplier
    #: on the RAW delta), or "reject" (nothing applied, ``eta`` = 0 and
    #: the iteration counter did not move).
    screen: str = "accept"


def _flatten_stats(upds: List[ClientUpdate]) -> dict:
    """Stats of a ``server.flatten`` span, read when it closes: ``staging``
    is "host" when deltas with NumPy leaves were joined on the host, any
    device delta among them read back first, and uploaded once
    (``pt.on_host``), and "device" when the staging program took the
    leaves as they were; ``h2d_bytes`` and ``d2h_bytes`` count the deltas'
    bytes that crossed each way."""
    deltas = [u.delta for u in upds]

    def back():
        if not pt.on_host(deltas):
            return 0
        return trace.nbytes(deltas) - trace.host_nbytes(deltas)
    return {"staging": lambda: "host" if pt.on_host(deltas) else "device",
            "h2d_bytes": lambda: trace.host_nbytes(deltas) + back(),
            "d2h_bytes": back}


class AsyncServer:
    """Base class for asynchronous servers (one aggregation per arrival)."""

    is_async = True

    def __init__(self, params: PyTree, fed: FedConfig):
        self.params = params
        self.fed = fed
        self.t = 1                       # global iteration (paper: x_1 initial)
        self.history: List[UpdateRecord] = []
        # norm screening (DESIGN.md §11): None when fed.screen == "off",
        # so defense-off runs carry zero extra state
        self.screen = screening.make_screen(fed)
        # compressed transport (DESIGN.md §13): lazily built spec for
        # decompressing CompressedDelta payloads back to pytree form on
        # paths that aggregate leafwise
        self._despec: Optional[pt.FlatSpec] = None

    def _delta_tree(self, delta) -> PyTree:
        """A delta in pytree form, whatever form it arrived in."""
        if not compression.is_compressed(delta):
            return delta
        if self._despec is None:
            self._despec = pt.FlatSpec(self.params, block=compression.BLOCK)
        return self._despec.unflatten(compression.dequantize(delta))

    def _decompress(self, upd: ClientUpdate) -> ClientUpdate:
        if compression.is_compressed(upd.delta):
            return dataclasses.replace(upd, delta=self._delta_tree(upd.delta))
        return upd

    def _delta_vec(self, delta) -> np.ndarray:
        """The flat delta vector as host f32 numpy (dequantized when it
        arrived in wire form) — what direction-based screens consume."""
        if compression.is_compressed(delta):
            return np.asarray(compression.dequantize(delta), np.float32)
        if self._despec is None:
            self._despec = pt.FlatSpec(self.params, block=compression.BLOCK)
        return np.asarray(self._despec.flatten(delta), np.float32)

    def _screen_delta(self, upd: ClientUpdate):
        """Norm-screen one arriving delta. Returns ``(upd', verdict,
        scale, raw_norm)``: ``upd'`` carries the clipped delta — or is
        None when the update is rejected outright; ``raw_norm`` is None
        when screening is off, so the off path builds records exactly as
        before screening existed. Compressed deltas are screened on their
        DEQUANTIZED norm — the values aggregation will apply — and clip
        verdicts scale them in transport form (exact on int8 scales).
        Direction screens (``needs_vector``, e.g. the cosine screen) also
        receive the flat delta vector itself."""
        if self.screen is None:
            return upd, "accept", 1.0, None
        raw = compression.delta_norm(upd.delta)
        if getattr(self.screen, "needs_vector", False):
            verdict, scale = self.screen.observe(
                raw, upd.client_id, vec=self._delta_vec(upd.delta))
        else:
            verdict, scale = self.screen.observe(raw, upd.client_id)
        if verdict == "reject":
            return None, verdict, 0.0, raw
        if verdict == "clip":
            upd = dataclasses.replace(
                upd, delta=compression.scale_delta(upd.delta, scale))
        return upd, verdict, scale, raw

    def screen_stats(self) -> Optional[dict]:
        """Accept/clip/reject counters + threshold state (None when
        screening is off). Surfaced through ``SimResult.summary()``."""
        return None if self.screen is None else self.screen.stats()

    def on_connect(self, client_id: int) -> ServerReply:
        raise NotImplementedError

    def on_update(self, upd: ClientUpdate) -> ServerReply:
        raise NotImplementedError

    def on_update_batch(self, upds: List[ClientUpdate]) -> List[ServerReply]:
        """Drain a burst of arrivals (simulator ``batch_window``). Default:
        apply one at a time, then hand every client the final model — in a
        windowed drain all clients resume from the window's result. A batch
        of one is exactly ``on_update``."""
        replies = [self.on_update(u) for u in upds]
        if len(replies) == 1:
            return replies
        return [ServerReply(self.params, self.t, r.k_next) for r in replies]

    def batch_limit(self) -> Optional[int]:
        """Largest burst this server's drain path digests at full kernel
        efficiency (None = no preference). The auto-window controller
        (events.AutoWindow) clamps its target batch to this."""
        return None

    def on_disconnect(self, client_id: int) -> None:
        """Population-mode hook: the client's session ended and it is NOT
        coming back for another round right now — drop any per-client
        server state registered at its last reply, so state scales with
        the in-flight cohort instead of every client ever contacted.
        Default: nothing registered."""

    def finalize(self, now: float) -> None:
        """Runtime end-of-run hook, called once when virtual time runs out.
        Default: nothing pending."""


class AsyncFedEDServer(AsyncServer):
    """Algorithm 1: Euclidean-distance staleness + adaptive eta_g and K.

    Two execution backends, selected with ``backend=``:

    * ``"pytree"`` — the reference: four jnp passes over the parameter
      pytree per update (Eq. 6 distance, delta norm, Eq. 5 AXPY).
    * ``"pallas"`` — flat-state runtime: the global model lives as ONE
      padded flat f32 vector (``pt.FlatParams``), the GMIS stores flat
      vectors, and every update runs through the fused fedagg kernels — a
      norms sweep and an AXPY sweep (DESIGN.md §4). Bursts drained via
      :meth:`on_update_batch` go through the multi-delta batched kernel.
      The kernels run compiled on TPU and interpreted on CPU
      (``fedagg.resolve_interpret``).
    """

    name = "asyncfeded"

    def __init__(self, params: PyTree, fed: FedConfig,
                 gmis_mode: str = "ring", per_leaf: bool = False,
                 backend: str = "pytree"):
        if backend not in ("pytree", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        if backend == "pallas" and per_leaf:
            raise ValueError("per-leaf staleness needs the pytree backend")
        self.backend = backend
        # model-axis sharding (DESIGN.md §14): >1 places the flat global
        # vector (and, via the GMIS pass-through, every snapshot) over
        # the `model` mesh axis and routes aggregation through the
        # sharded entry points — one cross-shard psum per Eq. 6 norm.
        self._shards = fed.model_shards if backend == "pallas" else 1
        if self._shards > 1:
            from repro.kernels.fedagg import sharded as _sharded
            self._sharded = _sharded
            self._agg = {
                name: functools.partial(getattr(_sharded, name),
                                        shards=self._shards)
                for name in _AGG_OPS}
        else:
            self._sharded = None
            self._agg = {name: getattr(ops, name) for name in _AGG_OPS}
        self._flat: Optional[pt.FlatParams] = None
        self._zeros = None
        super().__init__(params, fed)    # routes through the params setter
        self.per_leaf = per_leaf
        self.gmis_mode = gmis_mode
        if gmis_mode == "ring":
            self.gmis = RingGMIS(depth=fed.gmis_depth)
        elif gmis_mode == "displacement":
            self.gmis = DisplacementGMIS()
        else:
            raise ValueError(gmis_mode)
        self.gmis.append(self.t, self._gmis_state())
        self.kctl = AdaptiveK(fed.k_initial, fed.gamma_bar, fed.kappa,
                              fed.k_min, fed.k_max)

    # --- flat-state plumbing: ``params`` stays the canonical pytree view ---
    @property
    def params(self) -> PyTree:
        if self.backend == "pallas":
            if self._shards > 1 and self._flat._tree_cache is None:
                # the pytree view leaves the server (client downloads,
                # eval): built straight from the sharded vec its leaves
                # would stay committed to the fedagg mesh and clash with
                # whatever mesh a cohort fan-out stacks them onto — so
                # unflatten from a neutral host copy instead
                vec = self._flat.vec
                with trace.span("server.unflatten",
                                d2h_bytes=lambda: vec.nbytes):
                    self._flat._tree_cache = self._flat.spec.unflatten(
                        jnp.asarray(jax.device_get(vec)))
            return self._flat.tree       # lazily unflattened, cached
        return self._params

    @params.setter
    def params(self, value: PyTree) -> None:
        if self.backend == "pallas":
            # pad to BLOCK * shards so every model shard is a whole
            # number of kernel blocks — non-dividing true sizes are
            # absorbed by the (value-transparent) zero padding
            self._flat = pt.FlatParams.from_tree(
                value, block=ops._BLOCK * self._shards)
            self._zeros = None
            if self._shards > 1:
                self._flat = self._flat.replace(
                    self._sharded.place_flat(self._flat.vec, self._shards))
        else:
            self._params = value

    def _zeros_vec(self):
        """The zero vector filling the x_stale slot of the displacement
        kernels. Built on first use, so a ring-mode server never holds a
        spare model-sized buffer on the device."""
        if self._zeros is None:
            self._zeros = self._flat.spec.zeros()
            if self._shards > 1:
                self._zeros = self._sharded.place_flat(self._zeros,
                                                       self._shards)
        return self._zeros

    def _gmis_state(self):
        """What the GMIS stores: flat vectors under the pallas backend (a
        raw array is a one-leaf pytree, so Ring/Displacement code is
        unchanged), full pytrees otherwise."""
        return self._flat.vec if self.backend == "pallas" else self.params

    def save_checkpoint(self, directory: str,
                        step: Optional[int] = None) -> str:
        """Persist the global model. The pallas backend saves the PADDED
        flat vector with its shard-layout metadata (checkpoint.save_flat)
        — round-tripping through the pytree view would drop the layout —
        while the pytree backend saves the params pytree."""
        from repro import checkpoint
        step = self.t if step is None else step
        if self.backend == "pallas":
            return checkpoint.save_flat(
                self._flat.vec, self._flat.spec.n, directory, step,
                block=self._flat.spec.block, model_shards=self._shards)
        return checkpoint.save_pytree(self.params, directory, step)

    def restore_checkpoint(self, directory: str,
                           step: Optional[int] = None) -> None:
        """Restore the global model saved by :meth:`save_checkpoint`.
        Flat checkpoints validate the true-element count and re-pad to
        THIS server's layout, so a vector saved under one
        ``model_shards`` restores exactly under another."""
        from repro import checkpoint
        if self.backend == "pallas":
            vec, _ = checkpoint.restore_flat(
                directory, step, n=self._flat.spec.n,
                n_padded=self._flat.spec.n_padded)
            vec = jnp.asarray(vec)
            if self._shards > 1:
                vec = self._sharded.place_flat(vec, self._shards)
            self._flat = self._flat.replace(vec)
        else:
            self.params = checkpoint.restore_pytree(self.params,
                                                    directory, step)

    def _register(self, client_id: int) -> None:
        if self.gmis_mode == "displacement":
            self.gmis.register_snapshot(client_id, self.t, self._gmis_state())
        else:
            self.gmis.register_snapshot(client_id, self.t)

    def on_connect(self, client_id: int) -> ServerReply:
        self._register(client_id)
        return ServerReply(self.params, self.t, self.kctl.get(client_id))

    # ------------------------------------------------------------ backends --
    def _aggregate_pytree(self, upd: ClientUpdate):
        fed = self.fed
        if self.gmis_mode == "displacement":
            dist = self.gmis.distance_from(upd.client_id, upd.snapshot_iter,
                                           self.params)
            res = asyncfeded_aggregate_with_dist(
                self.params, dist, upd.delta, lam=fed.lam, eps=fed.eps,
                cap=fed.staleness_cap)
            self.gmis.release(upd.client_id)
        else:
            stale, _ = self.gmis.get(upd.snapshot_iter)
            agg = (asyncfeded_aggregate_per_leaf if self.per_leaf
                   else asyncfeded_aggregate)
            res = agg(self.params, stale, upd.delta, lam=fed.lam,
                      eps=fed.eps, cap=fed.staleness_cap)
        self.params = res.params
        return res.gamma, res.eta, res.dist, res.delta_norm, res.params

    def _wire_padded(self, cd):
        """A compressed payload's (q, scales) padded to the server's flat
        length. Clients pad to the kernel BLOCK; a sharded server pads to
        BLOCK * shards, which can be longer — appended zero q blocks
        carry zero scales and dequantize to exactly 0, so the extra
        padding stays value-transparent."""
        n_pad = self._flat.spec.n_padded
        if cd.q.shape[0] == n_pad:
            return cd.q, cd.scales
        q = jnp.pad(cd.q, (0, n_pad - cd.q.shape[0]))
        scales = cd.scales
        if scales is not None:
            scales = jnp.pad(
                scales, (0, n_pad // ops.fedagg.QBLOCK - scales.shape[0]))
        return q, scales

    def _aggregate_flat(self, upd: ClientUpdate):
        fed = self.fed
        cd = upd.delta if compression.is_compressed(upd.delta) else None
        # compressed payloads go to the kernels as they came (padded):
        # bf16 tiles upcast on load, int8 tiles dequantize with their
        # block scales (DESIGN.md §13)
        with trace.span("server.flatten", **_flatten_stats([upd])):
            d, scales = (self._wire_padded(cd) if cd is not None
                         else (self._flat.spec.flatten(upd.delta), None))
        # the AXPY writes x_{t+1} into d where the drain made d and reads
        # it no more: an f32 flatten (not the wire payload, nor a leaf of
        # the caller's that the flatten handed back as it was) on the
        # ring, whose on_aggregate ignores the delta
        in_place = (self.gmis_mode == "ring" and self._shards == 1
                    and cd is None and d.dtype == self._flat.vec.dtype
                    and all(d is not l for l in jax.tree.leaves(upd.delta)))
        displaced = self.gmis_mode == "displacement"
        with trace.span("server.kernels", in_place=int(in_place)):
            # displacement: Eq. 6 reads the maintained drift x_t - x_tau
            # against zeros, and the stale model is never materialized
            stale, drift = (
                (self._zeros_vec(), self.gmis.displacement(upd.client_id))
                if displaced else (self.gmis.get(upd.snapshot_iter)[0], None))
            new_vec, gamma, eta, dist, dnorm = self._agg["flat_aggregate"](
                self._flat.vec, stale, d, scales=scales, drift=drift,
                lam=fed.lam, eps=fed.eps, cap=fed.staleness_cap,
                in_place=in_place)
        if displaced:
            self.gmis.release(upd.client_id)
            if scales is not None:
                # the accumulators fold in the delta the AXPY applied
                d = compression.dequantize(
                    dataclasses.replace(cd, q=d, scales=scales))
        self._flat = self._flat.replace(new_vec)
        return gamma, eta, dist, dnorm, d

    def _reject_reply(self, upd: ClientUpdate, raw_norm: float
                      ) -> ServerReply:
        """A screened-out arrival: the model and the iteration counter do
        not move; the client simply resumes from the current model (its K
        unchanged — no gamma was observed)."""
        k_next = self.kctl.get(upd.client_id)
        self.history.append(UpdateRecord(
            self.t, upd.client_id, self.t - upd.snapshot_iter,
            float("nan"), 0.0, upd.k_used, k_next, float("nan"), raw_norm,
            "reject"))
        self._register(upd.client_id)
        return ServerReply(self.params, self.t, k_next)

    def on_update(self, upd: ClientUpdate) -> ServerReply:
        upd2, verdict, scale, raw_norm = self._screen_delta(upd)
        if upd2 is None:
            return self._reject_reply(upd, raw_norm)
        upd = upd2
        if self.backend == "pallas":
            gamma, eta, dist, dnorm, delta = self._aggregate_flat(upd)
        else:
            # decompress HERE, not inside _aggregate_pytree: the delta
            # also feeds gmis.on_aggregate below, which folds it into
            # every outstanding displacement accumulator leafwise
            upd = self._decompress(upd)
            gamma, eta, dist, dnorm, _ = self._aggregate_pytree(upd)
            delta = upd.delta
        # true staleness: tau = t - snapshot at APPLY time, before this
        # update advances the iteration counter — matches FedAsync's lag
        # telemetry so cross-server staleness records are comparable
        lag = self.t - upd.snapshot_iter
        self.t += 1
        # the host waits here for the aggregation's scalars
        with trace.span("server.sync", reads=3 + (raw_norm is None)):
            gamma_h, eta_h, dist_h = float(gamma), float(eta), float(dist)
            dnorm_h = float(dnorm) if raw_norm is None else raw_norm
        with trace.span("server.book"):
            self.gmis.append(self.t, self._gmis_state())
            self.gmis.on_aggregate(eta, delta)
            k_next = self.kctl.observe(upd.client_id, gamma_h)
            # history semantics under screening: eta is the effective
            # multiplier on the RAW arriving delta (eta * clip scale),
            # delta_norm the raw screening statistic; both collapse to the
            # plain aggregation scalars when screening is off
            self.history.append(UpdateRecord(
                self.t, upd.client_id, lag, gamma_h, eta_h * scale,
                upd.k_used, k_next, dist_h, dnorm_h, verdict))
            self._register(upd.client_id)
        return ServerReply(self.params, self.t, k_next)

    def on_update_batch(self, upds: List[ClientUpdate]) -> List[ServerReply]:
        """Burst path: B deltas through the multi-delta batched kernel in
        two grid sweeps, sequential-equivalent to B ``on_update`` calls
        (see ``aggregation.sequential_batch_schedule``). Only the ring-GMIS
        flat backend has the stacked stale models this needs; everything
        else — including a mixed-compression burst — falls back to the
        sequential default."""
        modes = {u.delta.mode if compression.is_compressed(u.delta)
                 else "off" for u in upds}
        # direction screens (cosine) consume the delta VECTOR, which the
        # batched Gram sweep never materializes per-update — they drain
        # sequentially through on_update's vector-aware path
        sequential = (self.backend != "pallas" or self.gmis_mode != "ring"
                      or len(upds) == 1 or len(modes) > 1
                      or getattr(self.screen, "needs_vector", False))
        with trace.span("server.drain", B=len(upds),
                        path="seq" if sequential else "batched"):
            if sequential:
                return self._drain_sequential(upds)
            return self._drain_batched(upds, modes.pop())

    def _drain_sequential(self, upds: List[ClientUpdate]
                          ) -> List[ServerReply]:
        replies = [self.on_update(u) for u in upds]
        if len(replies) > 1:
            # Every drained client resumes from the window's FINAL model,
            # so re-anchor their snapshot registrations there — in
            # displacement mode on_update zeroed each accumulator at an
            # intermediate model and then folded the remaining batch
            # updates into it, which would charge clients drift they never
            # experienced.
            for u in upds:
                self._register(u.client_id)
            replies = [ServerReply(self.params, self.t, r.k_next)
                       for r in replies]
        return replies

    def _drain_batched(self, upds: List[ClientUpdate], mode: str
                       ) -> List[ServerReply]:
        fed = self.fed
        spec = self._flat.spec
        # screening reuses the batched Gram sweep: the kernel-emitted raw
        # delta norms feed NormScreen in arrival order, and the returned
        # scale factors fold into the sequential-equivalence schedule
        # (etas come back as effective multipliers on the raw deltas).
        # Under compression those norms are the DEQUANTIZED ones — the
        # kernels compute every statistic on the transported values.
        screen_fn = (None if self.screen is None else
                     lambda dns: self.screen.decide_batch(
                         dns, [u.client_id for u in upds]))
        with trace.span("server.flatten", **_flatten_stats(upds)):
            stales = [self.gmis.get(u.snapshot_iter)[0] for u in upds]
            scales = None
            # "off" flattens pytrees; "bf16" and "int8" stack the padded
            # payloads, which the kernels decode tile by tile
            if mode == "off":
                stales, deltas = spec.stack(stales, [u.delta for u in upds])
            elif mode == "bf16":
                stales, deltas = pt.stack_rows(
                    stales, [self._wire_padded(u.delta)[0] for u in upds],
                    spec.n_padded)
            else:
                wires = [self._wire_padded(u.delta) for u in upds]
                stales = jnp.stack(stales)
                deltas = jnp.stack([q for q, _ in wires])
                scales = jnp.stack([s for _, s in wires])
        with trace.span("server.kernels", in_place=0):
            new_vec, etas, gammas, dists, dnorms, keep = (
                self._agg["flat_aggregate_batched"](
                    self._flat.vec, stales, deltas, scales=scales,
                    lam=fed.lam, eps=fed.eps, cap=fed.staleness_cap,
                    screen=screen_fn))
        self._flat = self._flat.replace(new_vec)
        k_nexts = []
        with trace.span("server.book"):
            for i, upd in enumerate(upds):
                verdict = ("accept" if keep is None
                           else screening.verdict_of_scale(float(keep[i])))
                # pre-increment staleness tau, exactly as in on_update:
                # the server state at this update's turn in the
                # sequential equivalence, before its own increment
                lag = self.t - upd.snapshot_iter
                if verdict == "reject":
                    k_next = self.kctl.get(upd.client_id)
                    self.history.append(UpdateRecord(
                        self.t, upd.client_id, lag, float("nan"), 0.0,
                        upd.k_used, k_next, float("nan"), float(dnorms[i]),
                        "reject"))
                else:
                    self.t += 1
                    gamma = float(gammas[i])
                    k_next = self.kctl.observe(upd.client_id, gamma)
                    self.history.append(UpdateRecord(
                        self.t, upd.client_id, lag, gamma,
                        float(etas[i]), upd.k_used, k_next, float(dists[i]),
                        float(dnorms[i]), verdict))
                k_nexts.append(k_next)
            # Intermediate models x_{t+1}..x_{t+B-1} are never handed to
            # any client (every drained client resumes from the window's
            # final model), so only the final version enters the GMIS.
            self.gmis.append(self.t, self._gmis_state())
            for upd in upds:
                self._register(upd.client_id)
        return [ServerReply(self.params, self.t, k) for k in k_nexts]

    def batch_limit(self) -> Optional[int]:
        if self.backend == "pallas" and self.gmis_mode == "ring":
            # compressed deltas cost fewer VMEM bytes per resident tile, so
            # the free-batch knee moves out: 15 (f32) -> 20 (bf16) -> 24
            # (int8) concurrent arrivals at full tile size
            delta_bytes = {"off": 4, "bf16": 2, "int8": 1}[
                self.fed.delta_compression]
            return ops.fedagg.batched_b_max(delta_bytes)
        return None

    def on_disconnect(self, client_id: int) -> None:
        """Release the snapshot registration made when this client's final
        reply was issued. Matters most in displacement mode, where a
        registration accumulates a displacement pytree on EVERY aggregation
        until released — a leak proportional to all contacted clients if
        pool-returning clients stayed registered."""
        self.gmis.release(client_id)


class FedAsyncServer(AsyncServer):
    """FedAsync (Xie et al. [43]): x <- (1-a) x + a x_local, with the
    paper's three staleness-decay functions s(lag) scaling the mixing
    weight alpha_t = alpha0 * s(t - tau):

    * ``constant`` — s = 1 (no decay);
    * ``poly``     — s = (lag + 1) ** -poly_a (polynomial decay);
    * ``hinge``    — s = 1 for lag <= b, else 1 / (a (lag - b) + 1).
    """

    MODES = ("constant", "poly", "hinge")

    def __init__(self, params: PyTree, fed: FedConfig, mode: str = "constant"):
        super().__init__(params, fed)
        assert mode in self.MODES, mode
        self.mode = mode
        self.name = f"fedasync+{mode}"
        self.gmis = RingGMIS(depth=fed.gmis_depth)
        self.gmis.append(self.t, params)

    def on_connect(self, client_id: int) -> ServerReply:
        return ServerReply(self.params, self.t, self.fed.k_initial)

    def _alpha(self, lag: int) -> float:
        a0 = self.fed.fedasync_alpha
        if self.mode == "constant":
            return a0
        if self.mode == "poly":
            return a0 * float(lag + 1) ** (-self.fed.poly_a)
        a, b = self.fed.hinge_a, self.fed.hinge_b
        s = 1.0 if lag <= b else 1.0 / (a * (lag - b) + 1.0)
        return a0 * s

    def on_update(self, upd: ClientUpdate) -> ServerReply:
        upd2, verdict, scale, raw_norm = self._screen_delta(upd)
        if upd2 is None:
            # rejected: nothing mixes, the counter does not move, the
            # client just resumes from the current model
            self.history.append(UpdateRecord(
                self.t, upd.client_id, self.t - upd.snapshot_iter,
                float("nan"), 0.0, upd.k_used, self.fed.k_initial,
                float("nan"), raw_norm, "reject"))
            return ServerReply(self.params, self.t, self.fed.k_initial)
        upd = self._decompress(upd2)     # mixing aggregates leafwise
        stale, actual = self.gmis.get(upd.snapshot_iter)
        x_local = pt.tree_add(stale, upd.delta)
        # the ring may have aged the requested snapshot out and clamped to
        # its oldest retained version: x_local above is rebuilt from that
        # clamped snapshot, so the staleness decay s(lag) must be
        # evaluated at the clamped lag too — not the un-clamped request
        lag = self.t - actual
        alpha = self._alpha(lag)
        self.params = jax.tree.map(
            lambda xg, xl: ((1.0 - alpha) * xg.astype(np.float32)
                            + alpha * xl.astype(np.float32)).astype(xg.dtype),
            self.params, x_local)
        self.t += 1
        self.gmis.append(self.t, self.params)
        self.history.append(UpdateRecord(
            self.t, upd.client_id, lag, float("nan"), alpha, upd.k_used,
            self.fed.k_initial, float("nan"),
            float("nan") if raw_norm is None else raw_norm, verdict))
        return ServerReply(self.params, self.t, self.fed.k_initial)


class FedBuffServer(AsyncServer):
    """FedBuff (Nguyen et al. [31]): buffered asynchronous aggregation."""

    name = "fedbuff"

    def __init__(self, params: PyTree, fed: FedConfig):
        super().__init__(params, fed)
        #: buffered (delta, snapshot_iter) pairs — snapshots kept so the
        #: flush can report the true staleness of its oldest contribution
        self.buffer: List[tuple] = []

    def on_connect(self, client_id: int) -> ServerReply:
        return ServerReply(self.params, self.t, self.fed.k_initial)

    def _flush(self, client_id: int, k_used: int) -> None:
        scale = self.fed.lam / len(self.buffer)
        # deltas are buffered in transport form (that's the memory win of
        # compression for FedBuff) and decompressed only at flush time
        mean = self._delta_tree(self.buffer[0][0])
        for d, _ in self.buffer[1:]:
            mean = pt.tree_add(mean, self._delta_tree(d))
        # staleness of the flush: its oldest buffered snapshot, measured
        # against the pre-increment iteration like every other server
        lag = self.t - min(snap for _, snap in self.buffer)
        self.params = pt.tree_axpy(scale, mean, self.params)
        self.buffer = []
        self.t += 1
        self.history.append(UpdateRecord(
            self.t, client_id, lag, float("nan"), scale, k_used,
            self.fed.k_initial, float("nan"), float("nan")))

    def on_update(self, upd: ClientUpdate) -> ServerReply:
        upd2, verdict, scale, raw_norm = self._screen_delta(upd)
        if upd2 is None:
            # rejected before buffering: the flush never sees this delta
            self.history.append(UpdateRecord(
                self.t, upd.client_id, self.t - upd.snapshot_iter,
                float("nan"), 0.0, upd.k_used, self.fed.k_initial,
                float("nan"), raw_norm, "reject"))
            return ServerReply(self.params, self.t, self.fed.k_initial)
        self.buffer.append((upd2.delta, upd2.snapshot_iter))
        if len(self.buffer) >= self.fed.fedbuff_size:
            self._flush(upd.client_id, upd.k_used)
        return ServerReply(self.params, self.t, self.fed.k_initial)

    def finalize(self, now: float) -> None:
        """Flush a partially filled buffer at end of run — scaled by the
        actual buffer size, like any flush — instead of silently dropping
        up to ``fedbuff_size - 1`` finished client rounds. Recorded in
        ``history`` with client_id -1 (no single contributing client)."""
        if self.buffer:
            self._flush(-1, 0)


class SyncServer:
    """Synchronous rounds (FedAvg Eq. 38; FedProx shares the rule — its
    difference is the client-side proximal term)."""

    is_async = False
    #: synchronous rounds aggregate a full cohort at once; norm screening
    #: is an async-arrival defense and stays off here
    screen = None

    def __init__(self, params: PyTree, fed: FedConfig, name: str = "fedavg"):
        self.params = params
        self.fed = fed
        self.name = name
        self.t = 1
        self.history: List[UpdateRecord] = []

    def screen_stats(self) -> Optional[dict]:
        return None

    def on_connect(self, client_id: int) -> ServerReply:
        return ServerReply(self.params, self.t, self.fed.k_initial)

    def round(self, updates: List[ClientUpdate]) -> ServerReply:
        total = float(sum(u.num_samples for u in updates))
        acc = None
        for u in updates:
            w = u.num_samples / total
            scaled = pt.tree_scale(u.delta, w)
            acc = scaled if acc is None else pt.tree_add(acc, scaled)
        self.params = pt.tree_add(self.params, acc)
        self.t += 1
        self.history.append(UpdateRecord(
            self.t, -1, 0, 0.0, 1.0, updates[0].k_used,
            self.fed.k_initial, 0.0, 0.0))
        return ServerReply(self.params, self.t, self.fed.k_initial)

    def finalize(self, now: float) -> None:
        """Runtime end-of-run hook; synchronous rounds leave nothing
        pending."""


def make_server(name: str, params: PyTree, fed: FedConfig, **kw):
    """Build a server by aggregator name. AsyncFedED variants accept
    ``backend="pytree"|"pallas"`` (flat-state fedagg-kernel runtime, see
    DESIGN.md §4.1) and ``gmis_mode`` via ``**kw``."""
    name = name.lower()
    if name == "asyncfeded":
        return AsyncFedEDServer(params, fed, **kw)
    if name == "asyncfeded-perleaf":
        return AsyncFedEDServer(params, fed, per_leaf=True, **kw)
    if name == "asyncfeded-displacement":
        return AsyncFedEDServer(params, fed, gmis_mode="displacement", **kw)
    if name == "fedasync+constant":
        return FedAsyncServer(params, fed, mode="constant", **kw)
    if name == "fedasync+poly":
        return FedAsyncServer(params, fed, mode="poly", **kw)
    if name == "fedasync+hinge":
        return FedAsyncServer(params, fed, mode="hinge", **kw)
    if name == "fedbuff":
        return FedBuffServer(params, fed, **kw)
    if name in ("fedavg", "fedprox"):
        return SyncServer(params, fed, name=name, **kw)
    raise ValueError(f"unknown aggregator {name!r}")
