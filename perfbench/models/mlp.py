"""The paper's Synthetic(1,1) task and its MLP, owned by the benchmark.

Weights, client data and mini-batches are made here from the run's seed,
so the program under test and the plain reference read the same inputs.
The data follows the Synthetic(alpha, beta) construction of Li et al.
(arXiv:1812.06127) that AsyncFedED uses (section 6.1, App. B.1), with one
change: every seed gets the same set of client sizes (power-law quantiles)
in another order, so a seed changes the rows and not the amount of work.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

from pb_numerics import mm

#: share of each client's rows held out for the evaluation batch
EVAL_SHARE = 0.1


def layer_dims(cfg: dict) -> list:
    return [cfg["input_dim"], *cfg["hidden"], cfg["num_classes"]]


def param_count(cfg: dict) -> int:
    dims = layer_dims(cfg)
    return sum(i * o + o for i, o in zip(dims[:-1], dims[1:]))


def make_weights(cfg: dict, seed: int):
    """Glorot-normal weights and zero biases, made on the device in one
    jitted call, in the program's ``{"fc<i>": {"w", "b"}}`` layout."""
    dims = tuple(layer_dims(cfg))
    if dims not in _INIT:
        _INIT[dims] = _init_fn(dims)
    return _INIT[dims](jax.random.PRNGKey(seed))


_INIT: dict = {}


def _init_fn(dims: tuple):
    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(dims) - 1)
        out = {}
        for i, (k, fi, fo) in enumerate(zip(keys, dims[:-1], dims[1:])):
            scale = (2.0 / (fi + fo)) ** 0.5
            out[f"fc{i}"] = {
                "w": jax.random.normal(k, (fi, fo), jnp.float32) * scale,
                "b": jnp.zeros((fo,), jnp.float32)}
        return out

    return init


def client_sizes(clients: int, base: int) -> list:
    """Rows per client: lognormal (sigma 0.7) quantiles around ``base``,
    at least 64, as the paper's power law; the same set for every seed."""
    nd = NormalDist()
    return [max(64, int(base * math.exp(0.7 * nd.inv_cdf((i + 0.5) / clients))))
            for i in range(clients)]


def make_data(cfg: dict, traffic: dict, seed: int):
    """Per-client training sets ``(x, y, stream)`` -- ``stream`` seeds the
    client's mini-batch draws -- and the evaluation batch."""
    ss = np.random.SeedSequence(seed)
    rng = np.random.default_rng(ss.spawn(1)[0])
    streams = [int(s.generate_state(1)[0])
               for s in ss.spawn(traffic["clients"])]
    dim, classes = cfg["input_dim"], cfg["num_classes"]
    sizes = client_sizes(traffic["clients"], traffic["samples_per_client"])
    sizes = [sizes[i] for i in rng.permutation(len(sizes))]
    std = np.arange(1, dim + 1, dtype=np.float64) ** -0.6   # Sigma_jj = j^-1.2
    train, ex, ey = [], [], []
    for n, stream in zip(sizes, streams):
        u = rng.normal(0.0, cfg["synthetic_alpha"])
        b_loc = rng.normal(0.0, cfg["synthetic_beta"])
        w = rng.normal(u, 1.0, size=(dim, classes))
        b = rng.normal(u, 1.0, size=(classes,))
        v = rng.normal(b_loc, 1.0, size=(dim,))
        x = v + rng.normal(size=(n, dim)) * std
        y = np.argmax(x @ w + b, axis=-1)
        x, y = x.astype(np.float32), y.astype(np.int32)
        n_eval = max(1, int(n * EVAL_SHARE))
        ex.append(x[:n_eval])
        ey.append(y[:n_eval])
        train.append((x[n_eval:], y[n_eval:], stream))
    return train, (np.concatenate(ex), np.concatenate(ey))


def make_batcher(dataset, cfg: dict, traffic: dict):
    return Batcher(dataset, cfg["fed"]["local_batch_size"])


class Batcher:
    """With-replacement mini-batches of one client's rows; ``next_stacked(k)``
    draws the same indices as ``k`` calls of ``next()``."""

    def __init__(self, dataset, batch_size: int):
        self.x, self.y, stream = dataset
        self.batch_size = min(batch_size, len(self.x))
        self.rng = np.random.default_rng(stream)

    def next(self):
        idx = self.rng.integers(0, len(self.x), size=self.batch_size)
        return self.x[idx], self.y[idx]

    def next_stacked(self, k: int):
        idx = self.rng.integers(0, len(self.x), size=(k, self.batch_size))
        return self.x[idx], self.y[idx]


def half_batch(batch):
    """The batch with its second half of rows left out."""
    x, y = batch
    h = x.shape[0] // 2
    return x[:h], y[:h]


def ref_loss(params, batch, cfg: dict, mode: str = "f32"):
    """Mean softmax cross-entropy of the ReLU MLP, in plain jnp."""
    x, y = batch
    h = x.astype(jnp.float32)
    n = len(params)
    for i in range(n):
        p = params[f"fc{i}"]
        h = mm("bi,io->bo", h, p["w"], mode) + p["b"]
        if i < n - 1:
            h = jax.nn.relu(h)
    logz = jax.nn.logsumexp(h, axis=-1)
    gold = jnp.take_along_axis(h, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def step_flops(cfg: dict, traffic: dict) -> float:
    """Model FLOPs of one local step, forward and backward: 2 per weight
    per row forward, 2 for the weight gradient, 2 for the input gradient
    of every layer but the first."""
    dims = layer_dims(cfg)
    rows = cfg["fed"]["local_batch_size"]
    per_row = sum((4 + (2 if l else 0)) * fi * fo
                  for l, (fi, fo) in enumerate(zip(dims[:-1], dims[1:])))
    return float(per_row * rows)
