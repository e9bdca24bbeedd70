"""The plain reference of one federated run's first drains.

Algorithm 1 and 2 of AsyncFedED in straightforward jnp and f32, with no
flat state, kernels, batching or program code:

* a client round is K steps of SGD with momentum on its own mini-batches,
  from the model it was handed, with the learning rate decayed per round
  (Eq. 2, 4); its update is the change of its weights;
* the server applies updates in arrival order: staleness
  gamma = ||x_t - x_stale|| / ||delta|| (Eq. 6, 0 when the server has not
  moved), eta = lam / (gamma + eps) (Eq. 7), x <- x + eta * delta (Eq. 5);
* the clients of one drain all resume from the drain's final model;
* the server keeps the models of its last ``gmis_depth`` drains (and the
  initial one until it falls out): an update whose model version is no
  longer kept has its staleness measured from the oldest one kept.

The arrival schedule -- which client arrives, in which drain, after how
many local steps, from which model version -- is the traffic; the replay
takes it from the run and recomputes every number.

Besides the run's own drains, ``replay_rounds`` and ``replay_drains``
recompute what set-up ran through the program from the seed's weights:
local rounds of a fresh client for each K, and drains of updates made
from the seed (``drain_updates``) for each burst size.
"""
from __future__ import annotations

import collections
import functools
import json
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_TINY = 1e-12


class Arrival(NamedTuple):
    client: int
    snapshot: int      # model version the client trained from
    k: int             # local steps


class Replay(NamedTuple):
    losses: List[float]          # mean local loss per arrival
    first_delta: np.ndarray      # leaf norms of the first arrival's update
    change: np.ndarray           # leaf norms of the model's change
    gammas: List[float]
    etas: List[float]
    rounds: list = []            # (loss, update leaf norms) per set-up round
    drains: list = []            # leaf norms of each set-up drain's change


_ROUND_FNS: dict = {}


def _round_fn(model, cfg: dict, mode: str, half: bool, beta: float):
    """Jitted client round, one per (configuration, precision, fault) and
    for every K: K steps of SGD with momentum over the first K of a stack
    of batches padded to the largest K."""
    key = (json.dumps(cfg, sort_keys=True), mode, half, beta)
    if key in _ROUND_FNS:
        return _ROUND_FNS[key]

    def loss_fn(params, batch):
        if half:
            batch = model.half_batch(batch)
        return model.ref_loss(params, batch, cfg, mode)

    @jax.jit
    def run(params, mu, xs, ys, k, lr):
        def step(i, carry):
            p, m, losses = carry
            batch = (jax.tree.map(lambda a: a[i], xs), ys[i])
            loss, g = jax.value_and_grad(loss_fn)(p, batch)
            m = jax.tree.map(lambda mi, gi: beta * mi + gi, m, g)
            p = jax.tree.map(lambda pi, mi: pi - lr * mi, p, m)
            return p, m, losses.at[i].set(loss)

        losses = jnp.zeros((ys.shape[0],), jnp.float32)
        p, m, losses = jax.lax.fori_loop(0, k, step, (params, mu, losses))
        delta = jax.tree.map(lambda a, b: a - b, p, params)
        return delta, m, jnp.sum(losses) / k

    _ROUND_FNS[key] = run
    return run


def _pad_steps(stack, k_max: int):
    """A stack of ``k`` batches (numpy, leading axis ``k``) padded with zeros
    to ``k_max``."""
    def pad(a):
        out = np.zeros((k_max, *a.shape[1:]), a.dtype)
        out[:a.shape[0]] = a
        return out
    return jax.tree.map(pad, stack)


def stale_versions(drains: List[List[Arrival]], depth: int) -> List[int]:
    """For each arrival, the model version the server measures its
    staleness from: its own while the server still keeps it, else the
    oldest one kept. The server keeps the models at the ends of its last
    ``depth`` drains, the initial model counting as the first."""
    kept = collections.deque([1], maxlen=depth)
    t, out = 1, []
    for drain in drains:
        for a in drain:
            out.append(a.snapshot if a.snapshot in kept else kept[0])
        t += len(drain)
        kept.append(t)
    return out


def _double_first_leaf(tree):
    leaves, tdef = jax.tree.flatten(tree)
    return jax.tree.unflatten(tdef, [2.0 * leaves[0], *leaves[1:]])


@jax.jit
def _apply(x, x_stale, delta, lam, eps):
    sq = lambda t: sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(t))
    dist = jnp.sqrt(sq(jax.tree.map(lambda a, b: a - b, x, x_stale)))
    dnorm = jnp.sqrt(sq(delta))
    gamma = jnp.where(dist <= _TINY, 0.0, dist / jnp.maximum(dnorm, _TINY))
    eta = lam / (gamma + eps)
    return jax.tree.map(lambda a, d: a + eta * d, x, delta), gamma, eta


@jax.jit
def leaf_norms(tree):
    """f32 Euclidean norm of every leaf, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree.leaves(tree)])


@jax.jit
def change_norms(a, b):
    """Per-leaf norms of ``a - b``."""
    return leaf_norms(jax.tree.map(lambda u, v: u.astype(jnp.float32) - v,
                                   a, b))


def replay(model, cfg: dict, traffic: dict, fed: dict, seed_weights: int,
           datasets, drains: List[List[Arrival]], *, mode: str = "f32",
           fault: str = "") -> Replay:
    """Recompute ``drains`` from the seed's weights and client data.

    ``mode`` is the precision of every matrix product (``pb_numerics``);
    ``fault`` plants a fault in the replay for the control readings:
    ``half_batch`` (every step's loss over half of its rows),
    ``altered_delta`` (the first update's first leaf doubled) or
    ``altered_round`` (that leaf doubled in every round of the largest K
    the traffic allows). Model
    versions are dropped once no later arrival trains or measures its
    staleness from them, so the replay holds little more than the
    clients' state on the device."""
    run_round = _round_fn(model, cfg, mode, fault == "half_batch",
                          float(fed["local_momentum"]))
    flat = [a for d in drains for a in d]
    # one shape for every run of the cell: the largest K the traffic allows
    k_max = max(int(fed["k_max"]), *(a.k for a in flat))
    lam, eps = jnp.float32(fed["lam"]), jnp.float32(fed["eps"])
    x1 = model.make_weights(cfg, seed_weights)
    stales = stale_versions(drains, int(fed["gmis_depth"]))
    last_use = {}
    for i, (a, s) in enumerate(zip(flat, stales)):
        last_use[a.snapshot] = last_use[s] = i
    versions: Dict[int, dict] = {1: x1}
    x, t, i = x1, 1, 0
    batchers, moms, rounds = {}, {}, {}
    losses, gammas, etas = [], [], []
    first_delta = None
    for drain in drains:
        for a in drain:
            c = a.client
            if c not in batchers:
                batchers[c] = model.make_batcher(datasets[c], cfg, traffic)
                moms[c] = jax.tree.map(jnp.zeros_like, x1)
                rounds[c] = 0
            xs, ys = _pad_steps(batchers[c].next_stacked(a.k), k_max)
            lr = fed["local_lr"] * fed["local_lr_decay"] ** rounds[c]
            delta, moms[c], l = run_round(
                versions[a.snapshot], moms[c], xs, ys, jnp.int32(a.k),
                jnp.float32(lr))
            rounds[c] += 1
            if (fault == "altered_delta" and first_delta is None
                    or fault == "altered_round" and a.k == int(fed["k_max"])):
                delta = _double_first_leaf(delta)
            if first_delta is None:
                first_delta = np.asarray(leaf_norms(delta))
            x, g, e = _apply(x, versions[stales[i]], delta, lam, eps)
            del delta
            for v in (a.snapshot, stales[i]):
                if v != 1 and last_use.get(v, -1) <= i:
                    versions.pop(v, None)
            t += 1
            i += 1
            losses.append(l)
            gammas.append(g)
            etas.append(e)
        if last_use.get(t, -1) >= i:
            versions[t] = x
    change = np.asarray(change_norms(x, x1))
    as_floats = lambda xs: [float(v) for v in jax.device_get(xs)]
    return Replay(as_floats(losses), first_delta, change, as_floats(gammas),
                  as_floats(etas))


def replay_rounds(model, cfg: dict, traffic: dict, fed: dict,
                  seed_weights: int, datasets, rounds, *, mode: str = "f32",
                  fault: str = "") -> list:
    """Each ``(client, k)`` of ``rounds`` as a fresh client's first local
    round from the seed's weights: ``(loss, update leaf norms)``. ``mode``
    and ``fault`` as for :func:`replay`."""
    run_round = _round_fn(model, cfg, mode, fault == "half_batch",
                          float(fed["local_momentum"]))
    k_max = max(int(fed["k_max"]), *(k for _, k in rounds))
    x1 = model.make_weights(cfg, seed_weights)
    mu = jax.tree.map(jnp.zeros_like, x1)
    out = []
    for c, k in rounds:
        batcher = model.make_batcher(datasets[c], cfg, traffic)
        xs, ys = _pad_steps(batcher.next_stacked(k), k_max)
        delta, _, loss = run_round(x1, mu, xs, ys, jnp.int32(k),
                                   jnp.float32(fed["local_lr"]))
        if fault == "altered_round" and k == int(fed["k_max"]):
            delta = _double_first_leaf(delta)
        out.append((loss, leaf_norms(delta)))
        del delta
    return [(float(l), np.asarray(n)) for l, n in jax.device_get(out)]


def drain_updates(template, seed: int, count: int) -> list:
    """``count`` updates shaped like ``template``, made on the device from
    ``seed`` in one jitted call: normal, each leaf scaled to a hundredth
    of its root mean square (1e-3 for a leaf of zeros)."""
    leaves, tdef = jax.tree.flatten(template)
    stacked = _drain_updates(jax.random.PRNGKey(seed), leaves, count)
    return [jax.tree.unflatten(tdef, [l[i] for l in stacked])
            for i in range(count)]


@functools.partial(jax.jit, static_argnums=2)
def _drain_updates(key, leaves, count):
    out = []
    for k, leaf in zip(jax.random.split(key, len(leaves)), leaves):
        leaf = leaf.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(jnp.square(leaf)))
        scale = jnp.where(rms > 0, 1e-2 * rms, 1e-3)
        out.append(scale * jax.random.normal(k, (count, *leaf.shape),
                                             jnp.float32))
    return out


def replay_drains(model, cfg: dict, fed: dict, seed_weights: int, seed: int,
                  sizes: List[int]) -> list:
    """Drains of ``sizes`` updates, one after another on one server, the
    i-th update of each drain being the i-th of ``drain_updates`` and
    trained from the initial model: the leaf norms of each drain's change
    of the model."""
    x1 = model.make_weights(cfg, seed_weights)
    updates = drain_updates(x1, seed, max(sizes))
    drains = [[Arrival(i, 1, 0) for i in range(b)] for b in sizes]
    stales = stale_versions(drains, int(fed["gmis_depth"]))
    lam, eps = jnp.float32(fed["lam"]), jnp.float32(fed["eps"])
    versions, x, t, j, out = {1: x1}, x1, 1, 0, []
    for drain in drains:
        before = x
        for i in range(len(drain)):
            x, _, _ = _apply(x, versions[stales[j]], updates[i], lam, eps)
            j += 1
        t += len(drain)
        if t in stales[j:]:
            versions[t] = x
        out.append(change_norms(x, before))
    return [np.asarray(n) for n in jax.device_get(out)]


def to_host(tree):
    return jax.tree.map(np.asarray, jax.device_get(tree))
