"""A Llama/Mistral-style decoder (RMSNorm, RoPE, grouped-query attention,
SwiGLU, untied head), owned by the benchmark: weights, token streams and a
plain-jnp reference loss.

The configuration file uses the Hugging Face ``config.json`` key names.
Weights are made in the program's parameter layout (``embed/tok``,
``layers/b0_attn/...`` stacked over layers, ``final_norm``, ``head/out``):
that layout is the interface the benchmark hands weights through, nothing
else of the program is used here.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from pb_numerics import mm

#: Zipf exponent of the synthetic token streams
ZIPF_S = 1.1


def dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return (d, h, cfg["num_key_value_heads"],
            cfg.get("head_dim") or d // h, cfg["intermediate_size"],
            cfg["vocab_size"], cfg["num_hidden_layers"])


def param_count(cfg: dict) -> int:
    d, h, kv, hd, f, v, n = dims(cfg)
    layer = 2 * d + 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f
    return 2 * v * d + n * layer + d


def matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix product per token (all but the
    embedding gather and the norm scales)."""
    d, h, kv, hd, f, v, n = dims(cfg)
    return n * (2 * d * h * hd + 2 * d * kv * hd + 3 * d * f) + d * v


def _shapes(cfg: dict) -> dict:
    d, h, kv, hd, f, v, n = dims(cfg)
    return {
        "embed": {"tok": ((v, d), "embed")},
        "layers": {"b0_attn": {
            "norm1": {"scale": ((n, d), "ones")},
            "attn": {"wq": ((n, d, h, hd), "w"), "wk": ((n, d, kv, hd), "w"),
                     "wv": ((n, d, kv, hd), "w"), "wo": ((n, h, hd, d), "w")},
            "norm2": {"scale": ((n, d), "ones")},
            "ffn": {"wi_gate": ((n, d, f), "w"), "wi_up": ((n, d, f), "w"),
                    "wo": ((n, f, d), "w")}}},
        "final_norm": {"scale": ((d,), "ones")},
        "head": {"out": ((d, v), "w")},
    }


def make_weights(cfg: dict, seed: int):
    """f32 weights made on the device in one jitted call: N(0, 1)
    embeddings, N(0, 0.02) projections, unit norm scales."""
    key = json.dumps(cfg, sort_keys=True)
    if key not in _INIT:
        _INIT[key] = _init_fn(cfg)
    return _INIT[key](jax.random.PRNGKey(seed))


_INIT: dict = {}


def _init_fn(cfg: dict):
    spec = _shapes(cfg)
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, kind) in zip(keys, leaves):
            if kind == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                std = 1.0 if kind == "embed" else 0.02
                out.append(jax.random.normal(k, shape, jnp.float32) * std)
        return jax.tree.unflatten(treedef, out)

    return init


def make_data(cfg: dict, traffic: dict, seed: int):
    """Per-client token-stream seeds and one evaluation batch. A client's
    dataset is the seed of its own stream."""
    ss = np.random.SeedSequence(seed)
    streams = [int(s.generate_state(1)[0])
               for s in ss.spawn(traffic["clients"] + 1)]
    eval_batch = Batcher(streams[-1], traffic, cfg).next()
    return streams[:-1], eval_batch


def make_batcher(dataset, cfg: dict, traffic: dict):
    return Batcher(dataset, traffic, cfg)


class Batcher:
    """Zipf-distributed token batches ``({"tokens": (b, s)}, labels)`` with
    labels the next token; ``next_stacked(k)`` equals ``k`` ``next()``."""

    def __init__(self, stream_seed: int, traffic: dict, cfg: dict):
        self.rng = np.random.default_rng(stream_seed)
        self.shape = (traffic["sequences"], traffic["seq_len"] + 1)
        p = np.arange(1, cfg["vocab_size"] + 1, dtype=np.float64) ** -ZIPF_S
        self.cdf = np.cumsum(p / p.sum())
        self.vocab = cfg["vocab_size"]

    def next(self):
        u = self.rng.random(self.shape)
        toks = np.minimum(np.searchsorted(self.cdf, u), self.vocab - 1)
        toks = toks.astype(np.int32)
        return {"tokens": toks[:, :-1]}, toks[:, 1:]

    def next_stacked(self, k: int):
        draws = [self.next() for _ in range(k)]
        return ({"tokens": np.stack([d[0]["tokens"] for d in draws])},
                np.stack([d[1] for d in draws]))


def half_batch(batch):
    """The batch with its second half of sequences left out."""
    inputs, labels = batch
    h = labels.shape[0] // 2
    return {"tokens": inputs["tokens"][:h]}, labels[:h]


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE over positions 0..s-1; x: (b, s, heads, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def ref_loss(params, batch, cfg: dict, mode: str = "f32"):
    """Mean next-token cross-entropy of the decoder, plain jnp in f32;
    ``mode`` rounds the operands of every matrix product."""
    d, h, kv, hd, f, v, n = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    window = cfg.get("sliding_window") or 0
    inputs, labels = batch
    tokens = inputs["tokens"]
    s = tokens.shape[1]
    x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask &= pos[None, :] > pos[:, None] - window
    lay = params["layers"]["b0_attn"]
    for i in range(n):
        a = jax.tree.map(lambda t: t[i], lay)
        y = _rms_norm(x, a["norm1"]["scale"], eps)
        q = _rope(mm("bsd,dhk->bshk", y, a["attn"]["wq"], mode), theta)
        k = _rope(mm("bsd,dhk->bshk", y, a["attn"]["wk"], mode), theta)
        val = mm("bsd,dhk->bshk", y, a["attn"]["wv"], mode)
        k = jnp.repeat(k, h // kv, axis=2)
        val = jnp.repeat(val, h // kv, axis=2)
        sc = mm("bqhk,bthk->bhqt", q, k, mode) * hd ** -0.5
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        o = mm("bhqt,bthk->bqhk", jax.nn.softmax(sc, axis=-1), val, mode)
        x = x + mm("bshk,hkd->bsd", o, a["attn"]["wo"], mode)
        y = _rms_norm(x, a["norm2"]["scale"], eps)
        g = mm("bsd,df->bsf", y, a["ffn"]["wi_gate"], mode)
        u = mm("bsd,df->bsf", y, a["ffn"]["wi_up"], mode)
        x = x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, a["ffn"]["wo"], mode)
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    logits = mm("bsd,dv->bsv", x, params["head"]["out"], mode)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def step_flops(cfg: dict, traffic: dict) -> float:
    """Model FLOPs of one local step: three times the forward pass (the
    backward pass costs two), no recompute. Forward: 2 per matrix weight
    per token, plus causal attention's two products, 2*b*heads*hd FLOPs
    per visible (query, key) pair each."""
    d, h, kv, hd, f, v, n = dims(cfg)
    b, s = traffic["sequences"], traffic["seq_len"]
    w = cfg.get("sliding_window") or s
    pairs = sum(min(q + 1, w) for q in range(s))
    fwd = 2.0 * b * s * matmul_params(cfg) + n * 2 * 2.0 * b * h * hd * pairs
    return 3.0 * fwd
