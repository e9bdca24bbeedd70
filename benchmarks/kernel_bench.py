"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels execute in interpret mode, so their
wall-times are NOT TPU-representative. What we report instead:

* wall time of the jnp REFERENCE paths (tree-based 4-pass aggregation vs
  flat fused 2-pass) — the host-side win of the fedagg layout is real even
  on CPU;
* structural metrics from compiled HLO: bytes accessed per aggregation
  variant (cost_analysis), which is the quantity the TPU kernel optimizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, save_json, time_call
from repro.core.aggregation import asyncfeded_aggregate
from repro.kernels.fedagg import fedagg
from repro.kernels.fedagg import ops as fedagg_ops
from repro.utils import pytree as pt
from repro.utils.xla import cost_analysis_dict


def _mock_params(n_leaves: int = 20, leaf: int = 50_000, seed: int = 0):
    keys = jax.random.split(jax.random.PRNGKey(seed), n_leaves)
    return {f"w{i}": jax.random.normal(k, (leaf,)) for i, k in enumerate(keys)}


def _flat_fused(xt, xs, d, lam, eps):
    """Flat single-fusion jnp aggregation (what the TPU kernel computes)."""
    diff = xt - xs
    dist = jnp.sqrt(jnp.sum(diff * diff))
    dn = jnp.sqrt(jnp.sum(d * d))
    gamma = jnp.where(dist <= 1e-12, 0.0, dist / jnp.maximum(dn, 1e-12))
    eta = lam / (gamma + eps)
    return xt + eta * d, gamma, eta


def run(n_leaves: int = 20, leaf: int = 50_000, batch: int = 8) -> dict:
    tree = _mock_params(n_leaves, leaf)
    stale = jax.tree.map(lambda x: x + 0.01, tree)
    delta = jax.tree.map(lambda x: x * 0.001, tree)
    n = pt.tree_size(tree)

    tree_fn = jax.jit(lambda a, b, c: asyncfeded_aggregate(
        a, b, c, lam=1.0, eps=1.0).params)
    us_tree = time_call(tree_fn, tree, stale, delta)

    xt = pt.tree_flatten_to_vector(tree)
    xs = pt.tree_flatten_to_vector(stale)
    d = pt.tree_flatten_to_vector(delta)
    flat_fn = jax.jit(lambda a, b, c: _flat_fused(a, b, c, 1.0, 1.0)[0])
    us_flat = time_call(flat_fn, xt, xs, d)

    # structural: bytes accessed per variant
    ca_tree = cost_analysis_dict(jax.jit(lambda a, b, c: asyncfeded_aggregate(
        a, b, c, lam=1.0, eps=1.0).params).lower(
        tree, stale, delta).compile())
    ca_flat = cost_analysis_dict(flat_fn.lower(xt, xs, d).compile())
    out = {
        "n_params": n,
        "tree_us": us_tree, "flat_us": us_flat,
        "speedup": us_tree / max(us_flat, 1e-9),
        "tree_bytes": float(ca_tree.get("bytes accessed", 0)),
        "flat_bytes": float(ca_flat.get("bytes accessed", 0)),
    }
    emit("kernel/fedagg_tree", us_tree, f"bytes={out['tree_bytes']:.3e}")
    emit("kernel/fedagg_flat_fused", us_flat,
         f"bytes={out['flat_bytes']:.3e};speedup={out['speedup']:.2f}x")
    out.update(run_batched(batch=batch, n_leaves=n_leaves, leaf=leaf))
    out.update(run_quant(batch=batch, n_leaves=n_leaves, leaf=leaf))
    out.update(run_sharded())
    save_json("kernel_bench", out)
    return out


def run_batched(batch: int = 8, n_leaves: int = 20, leaf: int = 50_000
                ) -> dict:
    """Burst-arrival path: B deltas through the multi-delta batched kernel
    (one norms sweep + one apply sweep + the host O(B^2) schedule) vs B
    sequential fedagg_fused calls — the one-at-a-time Pallas server loop it
    replaces. Both paths jit-cached and timed at steady state, in interpret
    mode on CPU; the structural win (2 sweeps instead of B, 1/B the
    pallas_call launches) is what carries to TPU."""
    tree = _mock_params(n_leaves, leaf)
    xt = fedagg_ops.pad_flat_vector(pt.tree_flatten_to_vector(tree))
    n = xt.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(7), 2 * batch)
    xs = jnp.stack([xt + 0.01 * jax.random.normal(k, (n,))
                    for k in keys[:batch]])
    ds = jnp.stack([0.001 * jax.random.normal(k, (n,))
                    for k in keys[batch:]])
    eta = jnp.float32(0.5)

    @jax.jit
    def sequential(x, stales, deltas):
        cur = x
        for i in range(batch):
            cur, _ = fedagg.fedagg_fused(cur, stales[i], deltas[i], eta)
        return cur

    def batched(x, stales, deltas):
        return fedagg_ops.flat_aggregate_batched(
            x, stales, deltas, lam=1.0, eps=1.0)[0]

    us_seq = time_call(sequential, xt, xs, ds, repeat=5)
    us_bat = time_call(batched, xt, xs, ds, repeat=5)
    out = {
        "batch": batch,
        "seq_fused_us": us_seq, "batched_us": us_bat,
        "batched_speedup": us_seq / max(us_bat, 1e-9),
    }
    emit(f"kernel/fedagg_seq_fused_x{batch}", us_seq, "")
    emit("kernel/fedagg_batched", us_bat,
         f"B={batch};speedup={out['batched_speedup']:.2f}x")
    return out


def run_quant(batch: int = 8, n_leaves: int = 20, leaf: int = 50_000
              ) -> dict:
    """Compressed-transport (DESIGN.md §13) metrics.

    Two kinds of rows:

    * deterministic structural metrics — int8 round-trip relative error on
      seeded data, the VMEM row-schedule batch knees per wire dtype
      (``batched_b_max``), wire bytes per parameter, and the cohort-width
      gain a 4 MiB model gets from int8 deltas under a fixed 224 MiB
      budget (the same crossing-interval construction the tests pin).
      These are what the compare.py gate pins: they do not move with
      machine load.
    * wall-time of the quant-fused norms+apply path vs dequantize-then-f32
      — interpret-mode CPU numbers, directional only (same caveat as every
      other row in this file).
    """
    from repro.configs.shapes import cohort_footprint_bytes, delta_wire_bytes
    from repro.core import compression

    tree = _mock_params(n_leaves, leaf, seed=11)
    xt = fedagg_ops.pad_flat_vector(pt.tree_flatten_to_vector(tree))
    n = xt.shape[0]
    key = jax.random.PRNGKey(13)
    d = 0.001 * jax.random.normal(key, (n,))
    cd = compression.quantize_vec(d, "int8", n)
    deq = compression.dequantize(cd)
    rel_err = float(jnp.linalg.norm(d - deq) / jnp.linalg.norm(d))

    # width ladder under a fixed budget: 4 MiB params, 16 clients, no
    # staged batches/activations — per-client cost is 3P + delta row, so
    # a 224 MiB budget sits exactly in the crossing interval where the
    # int8 delta row (P/4 + scales) doubles the placeable pow2 width
    P = 4 * 2 ** 20
    BUDGET = 224 * 2 ** 20

    def _width(db: int) -> int:
        w = 16
        while w > 2 and cohort_footprint_bytes(
                P, 0, 0, w, 1, delta_bytes=db) > BUDGET:
            w //= 2
        return w

    w_off = _width(delta_wire_bytes(P, "off"))
    w_int8 = _width(delta_wire_bytes(P, "int8"))

    xs = xt + 0.01 * jax.random.normal(jax.random.fold_in(key, 1), (n,))

    def fused_q(x, stale, q, scales):
        return fedagg_ops.flat_aggregate(x, stale, q, scales=scales,
                                         lam=1.0, eps=1.0)[0]

    @jax.jit
    def dequant_then_f32(x, stale, q, scales):
        dd = compression.dequantize(
            compression.CompressedDelta("int8", q, scales, n))
        cur, _ = fedagg.fedagg_fused(x, stale, dd, jnp.float32(0.5))
        return cur

    us_q = time_call(fused_q, xt, xs, cd.q, cd.scales, repeat=5)
    us_deq = time_call(dequant_then_f32, xt, xs, cd.q, cd.scales, repeat=5)

    b_f32 = fedagg.batched_b_max(4)
    b_int8 = fedagg.batched_b_max(1)
    out = {
        "int8_quant_rel_err": rel_err,
        "b_max_f32": b_f32,
        "b_max_bf16": fedagg.batched_b_max(2),
        "b_max_int8": b_int8,
        "b_max_gain_int8": b_int8 / b_f32,
        "wire_bytes_per_param_int8":
            compression.wire_bytes_per_param("int8"),
        "cohort_width_off": w_off,
        "cohort_width_int8": w_int8,
        "cohort_width_gain_int8": w_int8 / max(w_off, 1),
        "quant_fused_us": us_q,
        "dequant_then_f32_us": us_deq,
    }
    emit("kernel/fedagg_quant_fused", us_q,
         f"rel_err={rel_err:.2e};vs_dequant={us_deq / max(us_q, 1e-9):.2f}x")
    emit("kernel/batched_b_max", 0.0,
         f"f32={b_f32};bf16={out['b_max_bf16']};int8={b_int8};"
         f"gain_int8={out['b_max_gain_int8']:.2f}x")
    emit("kernel/cohort_width_gain_int8", 0.0,
         f"off={w_off};int8={w_int8};P=4MiB;budget=224MiB")
    return out


def run_sharded(shards: int = 8) -> dict:
    """Model-sharded flat state (DESIGN.md §14) structural metrics.

    Pure shape arithmetic — the gains sharding exists to buy, computable
    identically on a 1-device bench runner:

    * per-device flat-state footprint gain: the (2 + gmis_depth)-copy
      flat global state divided over the model axis (64 MiB params,
      depth-8 GMIS ring);
    * planned cohort-width gain: under a fixed budget, dividing each
      client's staged param state by the shard count lets the planner
      place a wider cohort (8 MiB params, 256 MiB budget, the same
      construction tests/test_flat_sharded.py pins).
    """
    from repro.configs.shapes import flat_state_bytes
    from repro.core.budget import plan_cohort
    from repro.core.tasks import arch_task

    P, DEPTH = 64 * 2 ** 20, 8
    full = flat_state_bytes(P, DEPTH)
    per_shard = flat_state_bytes(P, DEPTH, model_shards=shards)

    task = arch_task("h2o-danube-1.8b", seq_len=16, global_batch=2,
                     num_layers=1, d_model=64)
    kw = dict(clients=32, k=4, param_bytes=8 * 2 ** 20,
              budget_bytes=256 * 2 ** 20, pods=1)
    w1 = plan_cohort(task, task.fed, **kw).width
    ws = plan_cohort(task, task.fed, model_shards=shards, **kw).width
    out = {
        "model_shards": shards,
        "flat_state_gain_sharded": full / per_shard,
        "cohort_width_unsharded": w1,
        "cohort_width_sharded": ws,
        "cohort_width_gain_sharded": ws / max(w1, 1),
    }
    emit("kernel/flat_state_gain_sharded", 0.0,
         f"S={shards};P=64MiB;depth={DEPTH};"
         f"gain={out['flat_state_gain_sharded']:.2f}x")
    emit("kernel/cohort_width_gain_sharded", 0.0,
         f"S={shards};w1={w1};wS={ws};P=8MiB;budget=256MiB")
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--n-leaves", type=int, default=20)
    ap.add_argument("--leaf", type=int, default=50_000)
    ap.add_argument("--batch", type=int, default=8)
    a = ap.parse_args()
    print("name,us_per_call,derived")
    run(n_leaves=a.n_leaves, leaf=a.leaf, batch=a.batch)
