"""Client-side local training (Algorithm 2), generic over the task
substrate (repro.core.tasks).

A client downloads (x_t, K), performs K local SGD-with-momentum steps on
mini-batches of its own dataset (Eq. 2), and uploads the pseudo-gradient
Delta = x_K - x_0 (Eq. 4). Any optimizer is allowed (paper §4); we default
to momentum(0.5) with per-round lr decay 0.995 (Appendix B.4). The loss,
data sampler, and batch layout come from the :class:`LocalTask` — the
same client trains the paper's 60-float MLP rows and a reduced LLM's
token batches.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig
from repro.core import compression, tasks
from repro.core.server import ClientUpdate
from repro.utils import pytree as pt
from repro.utils import trace

PyTree = Any


def local_sgd_step(task, carry, bx, by, lr,
                   beta: float, prox_mu: float, anchor: PyTree):
    """One SGD-with-momentum step (Eq. 2) on one mini-batch.

    THE local optimizer step — shared by the per-client loop below and the
    cohort engine (repro.core.cohort), so the two engines cannot diverge.
    ``bx`` is the batch's inputs pytree (an array for the paper tasks, a
    token dict for arch tasks); ``by`` its targets. ``task`` may be any
    handle ``tasks.as_task`` accepts (coercion happens at trace time).
    FedProx: prox_mu > 0 anchors to the round's initial weights (Eq. 39).
    """
    task = tasks.as_task(task)
    p, m = carry
    prox = (prox_mu, anchor) if prox_mu > 0 else None
    loss, grads = jax.value_and_grad(
        lambda q: task.loss(q, (bx, by), prox=prox))(p)
    m = jax.tree.map(lambda mi, g: beta * mi + g, m, grads)
    p = jax.tree.map(lambda pi, mi: pi - lr * mi, p, m)
    return (p, m), loss


@functools.partial(jax.jit, static_argnames=("task", "beta", "prox_mu"))
def _local_k_steps(task, params: PyTree, mu_state: PyTree,
                   xs, ys, lr: jax.Array,
                   beta: float = 0.5, prox_mu: float = 0.0):
    """Scan K optimizer steps over stacked batches xs: (K, bs, ...) —
    leafwise when the inputs are a pytree.

    Returns (delta, new_momentum, mean_loss)."""

    def step(carry, batch):
        bx, by = batch
        return local_sgd_step(task, carry, bx, by, lr, beta,
                              prox_mu, params)

    (new_params, new_mu), losses = jax.lax.scan(step, (params, mu_state),
                                                (xs, ys))
    delta = pt.tree_sub(new_params, params)
    return delta, new_mu, jnp.mean(losses)


class Client:
    """One federated client: local data + persistent optimizer state."""

    def __init__(self, client_id: int, task, dataset, fed: FedConfig,
                 seed: int = 0):
        self.client_id = client_id
        self.task = tasks.as_task(task)
        self.fed = fed
        # seed derivation predates the substrate — byte-pinned streams
        self.batcher = self.task.make_batcher(
            dataset, fed.local_batch_size, seed * 10_007 + client_id)
        self.num_samples = self.task.num_samples(dataset)
        self.round_idx = 0
        self._mu: Optional[PyTree] = None
        # compressed transport (DESIGN.md §13): error-feedback residual —
        # the quantization error of the last emitted delta, folded into
        # the next one. Lives client-side like momentum; released on
        # session end (release_residual) like DisplacementGMIS state.
        self._residual: Optional[jax.Array] = None
        self._flatspec: Optional[pt.FlatSpec] = None

    def _lr(self) -> float:
        return self.fed.local_lr * (self.fed.local_lr_decay ** self.round_idx)

    # --- cohort-engine hooks (repro.core.cohort stacks many clients) ---
    def stage_cohort(self, params: PyTree):
        """Per-client state the cohort engine stacks on the host:
        (momentum, lr)."""
        if self._mu is None:
            self._mu = pt.tree_zeros_host(params)
        return self._mu, self._lr()

    def commit_cohort(self, mu: PyTree) -> None:
        """Scatter one cohort row back: new momentum + round bookkeeping,
        exactly what :meth:`run_local` does after ``_local_k_steps``."""
        self._mu = mu
        self.round_idx += 1

    def run_local(self, params: PyTree, k: int, snapshot_iter: int,
                  prox_mu: float = 0.0) -> Tuple[ClientUpdate, float]:
        """K local steps -> (ClientUpdate, mean local loss)."""
        if self._mu is None:
            self._mu = pt.tree_zeros_like(params)
        # next_stacked(k) is RNG-state-identical to k next() calls (pinned
        # by tests/test_cohort.py), so loop and cohort engines share streams
        with trace.span("client.stage",
                        h2d_bytes=lambda: trace.host_nbytes(bx, by)):
            bx, by = self.batcher.next_stacked(k)
            xs = jax.tree.map(jnp.asarray, bx)
            ys = jax.tree.map(jnp.asarray, by)
        delta, self._mu, loss = _local_k_steps(
            self.task, params, self._mu, xs, ys, jnp.float32(self._lr()),
            beta=self.fed.local_momentum, prox_mu=prox_mu)
        self.round_idx += 1
        upd = ClientUpdate(self.client_id, snapshot_iter, k, delta,
                           self.num_samples)
        with trace.span("client.sync", reads=1,
                        d2h_bytes=lambda: loss.nbytes):
            return upd, float(loss)

    # --- compressed transport (DESIGN.md §13) ---
    def compress_update(self, upd: ClientUpdate) -> ClientUpdate:
        """Quantize an outgoing update per ``fed.delta_compression``,
        folding in (and refreshing) the error-feedback residual.

        Called by the simulator at emission time, AFTER adversarial
        corruption — the attacker perturbs what the client computed; the
        wire carries what the attacker emitted. No-op when compression is
        off or the delta is already compressed (burst re-dispatch paths
        must not double-quantize)."""
        mode = self.fed.delta_compression
        if mode == "off" or compression.is_compressed(upd.delta):
            return upd
        if self._flatspec is None:
            self._flatspec = pt.FlatSpec(upd.delta, block=compression.BLOCK)
        vec = self._flatspec.flatten(upd.delta)
        if self._residual is not None:
            vec = vec + self._residual
        cd = compression.quantize_vec(vec, mode, self._flatspec.n)
        self._residual = vec - compression.dequantize(cd)
        return ClientUpdate(upd.client_id, upd.snapshot_iter, upd.k_used,
                            cd, upd.num_samples)

    def stage_residual(self, spec: pt.FlatSpec) -> jax.Array:
        """Cohort-engine hook (DESIGN.md §14): the error-feedback row the
        sharded engine folds into this client's delta before quantizing
        ON DEVICE. ``spec`` is the fan-out's shared flat layout, adopted
        as this client's flatspec so a later loop-engine
        :meth:`compress_update` keeps the identical padded length."""
        if self._flatspec is None:
            self._flatspec = spec
        if self._residual is None:
            return np.zeros((spec.n_padded,), np.float32)
        return self._residual

    def commit_residual(self, residual) -> None:
        """Scatter one refreshed error-feedback row back after the cohort
        engine compressed this client's delta itself
        (:meth:`compress_update` no-ops on the already wire-form
        update)."""
        self._residual = residual

    def release_residual(self) -> None:
        """Drop the error-feedback residual (client session ended)."""
        self._residual = None
