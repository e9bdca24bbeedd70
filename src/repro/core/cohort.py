"""Vectorized client-cohort engine (DESIGN.md §7), generic over the task
substrate (repro.core.tasks).

The reference client path trains one client per jitted call: every
``Client.run_local`` is its own dispatch, so a FedAvg round over C clients
pays C dispatches, C host stagings, and C blocking loss transfers, and the
client axis is never exposed to XLA. This module stacks per-client state
along a leading client axis — params snapshot, momentum, learning rate,
prox anchor, and the K mini-batches — and runs local training for the
whole cohort as ONE jitted vmap-over-clients / scan-over-K computation.
Batches are the substrate's ``(inputs, targets)`` pairs; inputs may be a
pytree (token dicts for the arch tasks), stacked leafwise.

Two jitted cores share the host-side orchestration:

* dense — every client runs the same K (sync FedAvg/FedProx rounds,
  initial async seeding): no masking, scan length is exactly K.
* masked — ragged per-client K (burst re-dispatch after adaptive K has
  diverged): scan length pads to a power-of-two bucket and a per
  ``(client, step)`` mask turns padded steps into exact no-ops — masked
  steps keep ``(params, momentum)`` bitwise unchanged and contribute zero
  loss, so heterogeneous ``k_next`` values share one compile.

The client axis pads to a power-of-two bucket in both cores (padded rows
are discarded), bounding distinct compilations to ``log2(C) * log2(K)``
buckets no matter how burst sizes vary over a run.

The ``cohort_sharded`` engine (DESIGN.md §8) wraps the SAME two core
bodies in ``shard_map`` over the ``pod`` axis of a 1-D client mesh
(``launch.mesh.make_cohort_mesh``): the padded client bucket splits into
equal per-pod shards (both are powers of two, so the split is always
even), each pod runs the vmap/scan core on its own sub-cohort, and only
the resulting deltas cross the pod boundary — at aggregation, on the
host, exactly as in the unsharded engine. All host-side orchestration
(batcher draws, staging order, commit order) is byte-identical across
engines, so the simulator's event trace and every client's RNG state are
engine-independent.

**Memory-budgeted execution** (DESIGN.md §10): ``run_cohort`` accepts a
:class:`repro.core.budget.CohortPlan`. A clamped ``plan.width`` splits the
client axis into power-of-two chunks dispatched sequentially; a clamped
``plan.k_chunk`` splits each chunk's K-scan into microbatch segments,
threading the ``(params, momentum)`` carry between segments on device and
summing the segment deltas (total delta and per-step loss mean are
unchanged — the scan is merely cut, not reordered). All batcher draws
still happen up front in client order, so a plan can never fork a
client's RNG stream.

**Compressed pod collectives** (DESIGN.md §14): under
``cohort_sharded`` with ``FedConfig.delta_compression`` set, the deltas
never cross the pod boundary as f32. A second shard_map'd step flattens
each pod's own stacked delta rows, folds in the clients' staged
error-feedback residual rows, and quantizes to transport form on device
— so the gather that ends the dispatch moves int8/bf16 wire blocks (the
same per-QBLOCK absmax layout as ``core.compression``) for the delta
payload, with the f32 residual rows scattered back to their clients as
per-pod error-feedback accounting. ``run_cohort`` then emits
:class:`~repro.core.compression.CompressedDelta` updates directly and
``Client.compress_update`` no-ops on them. One ordering consequence: an
adversary corrupts these updates in WIRE form (the attack fns have exact
wire-form twins for sign-flip/scale/zero), whereas the loop engine
corrupts the f32 pytree before quantization.

Semantics match the per-client loop exactly: the same batcher index
stream (``next_stacked`` is RNG-state-identical to k ``next`` calls), the
same momentum carry, the same per-round lr decay, the same FedProx
anchor. Equivalence is pinned by ``tests/test_cohort.py`` and
``tests/test_cohort_sharded.py`` on both server backends, including
ragged K and client counts that don't divide the pod count.
"""
from __future__ import annotations

import functools
from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CLIENT_ENGINES
from repro.core import compression
from repro.core import tasks as tasks_mod
from repro.core.client import local_sgd_step
from repro.core.server import ClientUpdate
from repro.launch import mesh as mesh_lib
from repro.sharding import specs as sh
from repro.utils import pytree as pt
from repro.utils import trace

PyTree = Any

#: valid values of ``FedConfig.client_engine`` (defined in configs.base so
#: the config layer validates without importing engine code)
ENGINES = CLIENT_ENGINES

#: engines this module executes (everything but the per-client loop)
COHORT_ENGINES = ("cohort", "cohort_sharded")


def bucket_size(n: int) -> int:
    """Next power of two >= n (n >= 1): the shared pad size that lets
    ragged client counts and per-client K values reuse one compile."""
    if n < 1:
        raise ValueError(f"bucket_size needs n >= 1, got {n}")
    return 1 << (int(n) - 1).bit_length()


def _dense_body(task, params: PyTree, mu: PyTree,
                xs, ys, lrs: jax.Array,
                beta: float, prox_mu: float):
    """Uniform-K core body: vmap over clients, scan over exactly K steps.

    ``params``/``mu``: pytrees stacked ``(C, ...)``; ``xs``: the inputs
    pytree stacked ``(C, K, bs, ...)`` leafwise; ``lrs``: ``(C,)`` f32.
    Returns ``(deltas, new_mu, mean_losses)`` stacked along the client
    axis. Shared by the jitted single-device core and the per-pod shard of
    the sharded core — a pod's shard is just a smaller C.
    """

    def one_client(p0, m0, xs_c, ys_c, lr):
        def step(carry, batch):
            bx, by = batch
            return local_sgd_step(task, carry, bx, by, lr,
                                  beta, prox_mu, p0)

        (p_k, m_k), losses = jax.lax.scan(step, (p0, m0), (xs_c, ys_c))
        return pt.tree_sub(p_k, p0), m_k, jnp.mean(losses)

    return jax.vmap(one_client)(params, mu, xs, ys, lrs)


def _masked_body(task, params: PyTree, mu: PyTree,
                 xs, ys, lrs: jax.Array,
                 mask: jax.Array, beta: float, prox_mu: float):
    """Ragged-K core body: like :func:`_dense_body` plus a ``(C, K)`` f32
    step mask — a zero entry keeps that client's ``(params, momentum)``
    carry bitwise unchanged and contributes zero loss, so client i's
    result equals a k_i-step run regardless of the padded scan length.
    Losses average over active steps only, matching the loop's mean over
    exactly k losses.
    """

    def one_client(p0, m0, xs_c, ys_c, lr, mask_c):
        def step(carry, inp):
            bx, by, act = inp
            (p2, m2), loss = local_sgd_step(task, carry, bx, by, lr, beta,
                                            prox_mu, p0)
            keep = act > 0
            p = jax.tree.map(lambda new, old: jnp.where(keep, new, old),
                             p2, carry[0])
            m = jax.tree.map(lambda new, old: jnp.where(keep, new, old),
                             m2, carry[1])
            return (p, m), loss * act

        (p_k, m_k), losses = jax.lax.scan(step, (p0, m0),
                                          (xs_c, ys_c, mask_c))
        mean_loss = jnp.sum(losses) / jnp.maximum(jnp.sum(mask_c), 1.0)
        return pt.tree_sub(p_k, p0), m_k, mean_loss

    return jax.vmap(one_client)(params, mu, xs, ys, lrs, mask)


@functools.partial(jax.jit, static_argnames=("task", "beta", "prox_mu"))
def _cohort_dense(task, params: PyTree, mu: PyTree,
                  xs, ys, lrs: jax.Array,
                  beta: float = 0.5, prox_mu: float = 0.0):
    return _dense_body(task, params, mu, xs, ys, lrs, beta, prox_mu)


@functools.partial(jax.jit, static_argnames=("task", "beta", "prox_mu"))
def _cohort_masked(task, params: PyTree, mu: PyTree,
                   xs, ys, lrs: jax.Array,
                   mask: jax.Array, beta: float = 0.5,
                   prox_mu: float = 0.0):
    return _masked_body(task, params, mu, xs, ys, lrs, mask, beta, prox_mu)


@functools.lru_cache(maxsize=None)
def _sharded_core(task, n_pods: int, masked: bool,
                  beta: float, prox_mu: float):
    """Jitted ``shard_map`` wrapper of the core bodies over a ``pod`` mesh.

    Every operand carries the stacked client axis in front, so one prefix
    spec (`sharding.specs.COHORT_PREFIX_SPEC`) shards them all — each
    pytree operand's leaves included: each pod receives ``C_pad /
    n_pods`` client rows — its own params/momentum slices, mini-batches,
    lrs and step masks — and runs the exact vmap-over-clients/scan-over-K
    body on them. There is NO collective inside local training; the
    deltas come back pod-sharded and cross the boundary only when the
    server aggregates them (DESIGN.md §8).

    Cached per ``(task, n_pods, masked, beta, prox_mu)``: the mesh is
    process-global state, and jit caching below a shard_map closure is
    keyed on the wrapped callable's identity.
    """
    mesh = mesh_lib.make_cohort_mesh(n_pods)
    spec = sh.COHORT_PREFIX_SPEC

    if masked:
        def body(params, mu, xs, ys, lrs, mask):
            return _masked_body(task, params, mu, xs, ys, lrs, mask,
                                beta, prox_mu)
        n_in = 6
    else:
        def body(params, mu, xs, ys, lrs):
            return _dense_body(task, params, mu, xs, ys, lrs,
                               beta, prox_mu)
        n_in = 5
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,) * n_in,
                       out_specs=(spec, spec, spec))
    return jax.jit(fn)


def _pad_steps(batch, k_pad: int):
    """Pad a (k, bs, ...) batch pytree to k_pad steps, leafwise, by
    repeating the last real batch (valid data — masked out, never
    applied)."""
    k = jax.tree.leaves(batch)[0].shape[0]
    if k == k_pad:
        return batch
    reps = k_pad - k
    return jax.tree.map(
        lambda a: np.concatenate([a, np.repeat(a[-1:], reps, axis=0)]),
        batch)


def _core_call(task, engine: str, fed, p_stacked, mu_stacked, xs, ys,
               lrs, mask, prox_mu: float, c_pad: int):
    """One core invocation: the engine/mask dispatch every chunk and every
    K-segment funnels through."""
    uniform = mask is None
    if engine == "cohort_sharded":
        # Per-pod client bucketing: c_pad and n_pods are both powers of
        # two with n_pods <= c_pad, so every pod gets exactly
        # c_pad / n_pods stacked rows — no per-pod raggedness, one
        # compile per (bucket, pod-count) pair.
        n_pods = mesh_lib.pod_count(max_pods=c_pad)
        core = _sharded_core(task, n_pods, not uniform,
                             fed.local_momentum, float(prox_mu))
        if uniform:
            return core(p_stacked, mu_stacked, xs, ys, jnp.asarray(lrs))
        return core(p_stacked, mu_stacked, xs, ys, jnp.asarray(lrs),
                    jnp.asarray(mask))
    if uniform:
        return _cohort_dense(task, p_stacked, mu_stacked, xs, ys,
                             jnp.asarray(lrs), beta=fed.local_momentum,
                             prox_mu=prox_mu)
    return _cohort_masked(task, p_stacked, mu_stacked, xs, ys,
                          jnp.asarray(lrs), jnp.asarray(mask),
                          beta=fed.local_momentum, prox_mu=prox_mu)


@functools.lru_cache(maxsize=None)
def _wire_core(n_pods: int, mode: str):
    """Jitted shard_map'd per-pod delta compressor (DESIGN.md §14).

    Each pod flattens its OWN stacked delta rows (leafwise ravel+concat —
    the exact ``FlatSpec`` staging order), adds the staged error-feedback
    residual rows, and quantizes row-wise with the same per-QBLOCK absmax
    math as ``compression._quantize_int8``. The delta payload leaves the
    device in wire form; the refreshed residual rows return as NEUTRAL
    host arrays — client state must not stay committed to this dispatch's
    pod mesh, or the commitment would propagate through the next
    ``compress_update`` into server params and clash with a
    differently-sized mesh on a later fan-out.
    """
    mesh = mesh_lib.make_cohort_mesh(n_pods)
    spec = sh.COHORT_PREFIX_SPEC

    def body(deltas, res):
        rows = jnp.concatenate(
            [l.reshape(l.shape[0], -1).astype(jnp.float32)
             for l in jax.tree.leaves(deltas)], axis=1)
        if rows.shape[1] != res.shape[1]:
            rows = jnp.pad(rows, ((0, 0), (0, res.shape[1] - rows.shape[1])))
        vec = rows + res
        if mode == "int8":
            blocks = vec.reshape(vec.shape[0], -1, compression.QBLOCK)
            absmax = jnp.max(jnp.abs(blocks), axis=2)
            scales = absmax / 127.0
            inv = jnp.where(scales > 0,
                            1.0 / jnp.where(scales > 0, scales, 1.0), 0.0)
            q = jnp.clip(jnp.round(blocks * inv[:, :, None]),
                         -127, 127).astype(jnp.int8)
            deq = (q.astype(jnp.float32) * scales[:, :, None]
                   ).reshape(vec.shape)
            return q.reshape(vec.shape[0], -1), scales, vec - deq
        q = vec.astype(jnp.bfloat16)
        return q, vec - q.astype(jnp.float32)

    n_out = 3 if mode == "int8" else 2
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                       out_specs=(spec,) * n_out)
    return jax.jit(fn)


def _wire_finish(deltas, res_stacked, mode: str, c_pad: int):
    """Run the per-pod compressor and gather the wire blocks to the host.

    Returns ``(q, scales, new_res)`` as host arrays in transport dtypes
    (``scales`` is None for bf16); the delta payload crosses in int8/bf16
    while the f32 residual stack is per-client error-feedback STATE, not
    part of the aggregated wire traffic.
    """
    n_pods = mesh_lib.pod_count(max_pods=c_pad)
    out = _wire_core(n_pods, mode)(deltas, res_stacked)
    if mode == "int8":
        return jax.device_get(out)
    q, new_res = jax.device_get(out)
    return q, None, new_res


# stack per-client trees on the host: jnp.stack would dispatch
# expand_dims+concat per client per leaf (hundreds of ops per round);
# momentum rows come back as np views from the previous device_get,
# so np.stack is a plain memcpy
_np_stack = functools.partial(jax.tree.map,
                              lambda *ls: np.stack([np.asarray(x)
                                                    for x in ls]))


def _run_chunk(task, fed, engine: str, p_src, mus, lrs_list, x_rows,
               y_rows, ks: Sequence[int], prox_mu: float, template,
               k_chunk: Optional[int], wire=None):
    """Execute one client chunk: pad/stack, then run the core — in one
    call, or in ``k_chunk``-step scan segments when the memory plan says
    the full K-scan doesn't fit. Returns (deltas, new_mu, losses,
    wire_out) stacked over the chunk's real clients (padding discarded by
    the caller via row index). ``wire`` is ``(mode, residual_rows)`` for
    the compressed pod collective: the chunk then returns ``wire_out =
    (q, scales, new_res)`` in place of f32 ``deltas`` (which come back
    None)."""
    c_real = len(mus)
    c_pad = bucket_size(c_real)
    uniform = len(set(ks)) == 1
    k_pad = ks[0] if uniform else bucket_size(max(ks))

    xs_rows, ys_rows = [], []
    lrs = np.zeros((c_pad,), np.float32)
    mask = None if uniform else np.zeros((c_pad, k_pad), np.float32)
    for i, k in enumerate(ks):
        bx, by = x_rows[i], y_rows[i]
        if not uniform:
            bx = _pad_steps(bx, k_pad)
            by = _pad_steps(by, k_pad)
            mask[i, :k] = 1.0
        xs_rows.append(bx)
        ys_rows.append(by)
        lrs[i] = lrs_list[i]
    zeros_mu = pt.tree_zeros_host(template)
    mus = list(mus)
    for _ in range(c_pad - c_real):    # padded client rows: discarded
        xs_rows.append(xs_rows[0])
        ys_rows.append(ys_rows[0])
        mus.append(zeros_mu)

    res_stacked = wire_mode = None
    if wire is not None:
        wire_mode, res_rows = wire
        rows = [np.asarray(r, np.float32) for r in res_rows]
        rows += [np.zeros_like(rows[0])] * (c_pad - c_real)
        res_stacked = np.stack(rows)

    # the host arrays staged here are uploaded by the core's dispatch
    with trace.span("client.stage", h2d_bytes=lambda: trace.host_nbytes(
            xs, ys, mu_stacked, p_stacked, lrs, mask)):
        xs = _np_stack(*xs_rows)
        ys = _np_stack(*ys_rows)
        mu_stacked = _np_stack(*mus)
        if isinstance(p_src, list):
            p_stacked = _np_stack(*(p_src + [template] * (c_pad - c_real)))
        else:                          # shared snapshot: broadcast on device
            p_stacked = jax.tree.map(
                lambda p: jnp.broadcast_to(p, (c_pad,) + p.shape), p_src)

    if k_chunk is None or k_chunk >= k_pad:
        deltas, new_mu, losses = _core_call(task, engine, fed, p_stacked,
                                            mu_stacked, xs, ys, lrs, mask,
                                            prox_mu, c_pad)
        if wire_mode is None:
            out = (deltas, new_mu, losses)
            with trace.span("client.sync", reads=1,
                            d2h_bytes=lambda: trace.nbytes(out)):
                return (*jax.device_get(out), None)
        wire_out = _wire_finish(deltas, res_stacked, wire_mode, c_pad)
        return None, *jax.device_get((new_mu, losses)), wire_out

    # --- K-scan microbatches: thread the (params, momentum) carry through
    # segments on device; total delta is the sum of segment deltas and the
    # per-step loss mean is reassembled from segment sums. The FedProx
    # anchor would differ per segment, so the planner never chunks K when
    # prox_mu > 0.
    assert prox_mu == 0.0, "K-microbatching is undefined under FedProx"
    p_cur, mu_cur = p_stacked, mu_stacked
    delta_acc = None
    loss_sum = np.zeros((c_pad,), np.float64)
    for s0 in range(0, k_pad, k_chunk):
        s1 = min(s0 + k_chunk, k_pad)
        xs_seg = jax.tree.map(lambda a: a[:, s0:s1], xs)
        ys_seg = jax.tree.map(lambda a: a[:, s0:s1], ys)
        mask_seg = None if uniform else mask[:, s0:s1]
        d, mu_cur, l_seg = _core_call(task, engine, fed, p_cur, mu_cur,
                                      xs_seg, ys_seg, lrs, mask_seg,
                                      prox_mu, c_pad)
        # segment means -> per-client loss sums (dense: mean * seg_len;
        # masked: mean over active steps * active count)
        act = (float(s1 - s0) if uniform
               else mask_seg.sum(axis=1).astype(np.float64))
        loss_sum += np.asarray(jax.device_get(l_seg), np.float64) * act
        p_cur = pt.tree_add(p_cur, d)
        delta_acc = d if delta_acc is None else pt.tree_add(delta_acc, d)
    total_act = (np.full((c_pad,), float(k_pad))
                 if uniform else np.maximum(mask.sum(axis=1), 1.0))
    losses = (loss_sum / total_act).astype(np.float32)
    if wire_mode is not None:
        # segment deltas were accumulated on device, so the compressed
        # gather still sees ONE full-K delta per client row
        wire_out = _wire_finish(delta_acc, res_stacked, wire_mode, c_pad)
        return None, jax.device_get(mu_cur), losses, wire_out
    deltas, new_mu = jax.device_get((delta_acc, mu_cur))
    return deltas, new_mu, losses, None


def run_cohort(task, clients: Sequence,
               params: Union[PyTree, Sequence[PyTree]], ks: Sequence[int],
               snapshot_iters: Sequence[int], prox_mu: float = 0.0,
               per_client_params: bool = False, engine: str = "cohort",
               plan=None) -> List[Tuple[ClientUpdate, float]]:
    """Train ``clients`` for ``ks`` local steps each in one jitted call.

    Drop-in replacement for ``[c.run_local(params, k, it, prox_mu) for
    ...]`` (same batcher streams, momentum carry, round_idx/lr schedule),
    equivalent to float tolerance. ``task`` is any handle
    ``tasks.as_task`` accepts (a LocalTask, a raw PaperTaskConfig, ...).
    ``params`` is one shared snapshot pytree (every fan-out site — sync
    rounds, async seeding, burst re-dispatch — hands the whole cohort the
    same downloaded model), broadcast along the client axis. With
    ``per_client_params=True`` it is instead a length-C sequence of
    snapshots, stacked leafwise. The flag is explicit rather than
    inferred from ``isinstance`` so a future list-rooted params pytree
    cannot be misread as a per-client sequence.

    ``engine`` selects the execution core: ``"cohort"`` runs the whole
    stacked cohort on one device; ``"cohort_sharded"`` shards the client
    axis over a ``pod`` mesh (as many pods as devices allow, capped at
    the padded client bucket so shards stay equal-sized). Host-side
    orchestration — and therefore every batcher's RNG state — is
    identical either way.

    ``plan`` (a :class:`repro.core.budget.CohortPlan`) bounds the device
    footprint: the client axis splits into ``plan.width``-sized chunks
    and each chunk's K-scan into ``plan.k_chunk``-step segments. With no
    plan (or a plan that fits) the dispatch is the single stacked call.
    """
    if engine not in COHORT_ENGINES:
        raise ValueError(f"run_cohort got engine {engine!r}: expected one "
                         f"of {COHORT_ENGINES} ('loop' is Client.run_local)")
    c_real = len(clients)
    if c_real == 0:
        return []
    if not (len(ks) == len(snapshot_iters) == c_real):
        raise ValueError("clients / ks / snapshot_iters length mismatch")
    task = tasks_mod.as_task(task)

    per_client = per_client_params
    if per_client:
        if len(params) != c_real:
            raise ValueError("per_client_params needs one snapshot per "
                             f"client, got {len(params)} for {c_real}")
        if all(p is params[0] for p in params):
            params, per_client = params[0], False
    template = params[0] if per_client else params

    # --- stage every client up front, in client order: batcher draws and
    # momentum staging happen identically under every plan/engine, so the
    # RNG streams can never fork on a memory fallback
    mus, lrs_list, x_rows, y_rows = [], [], [], []
    with trace.span("client.stage"):
        for c, k in zip(clients, ks):
            mu, lr = c.stage_cohort(template)
            bx, by = c.batcher.next_stacked(k)
            mus.append(mu)
            lrs_list.append(lr)
            x_rows.append(bx)
            y_rows.append(by)

    fed = clients[0].fed
    width = c_real
    k_chunk = None
    if plan is not None:
        width = max(1, min(int(plan.width), c_real))
        if prox_mu == 0.0 and int(plan.k_chunk) < max(ks):
            k_chunk = int(plan.k_chunk)

    # compressed pod collectives (DESIGN.md §14): the sharded engine
    # quantizes delta rows per pod, so the gather moves wire blocks
    res_spec = None
    res_rows: List = []
    if engine == "cohort_sharded" and fed.delta_compression != "off":
        res_spec = pt.FlatSpec(template, block=compression.BLOCK)
        res_rows = [c.stage_residual(res_spec) for c in clients]

    deltas_rows, mu_rows, loss_rows, res_commits = [], [], [], []
    for lo in range(0, c_real, width):
        hi = min(lo + width, c_real)
        if per_client:
            p_src = list(params[lo:hi])
        else:
            p_src = params
        wire_arg = (None if res_spec is None
                    else (fed.delta_compression, res_rows[lo:hi]))
        deltas, new_mu, losses, wire_out = _run_chunk(
            task, fed, engine, p_src, mus[lo:hi], lrs_list[lo:hi],
            x_rows[lo:hi], y_rows[lo:hi], ks[lo:hi], prox_mu, template,
            k_chunk, wire_arg)
        for i in range(hi - lo):
            if wire_out is not None:
                q, scales, new_res = wire_out
                deltas_rows.append(compression.CompressedDelta(
                    fed.delta_compression, q[i],
                    None if scales is None else scales[i], res_spec.n))
                res_commits.append(new_res[i])
            else:
                deltas_rows.append(jax.tree.map(lambda l: l[i], deltas))
            mu_rows.append(jax.tree.map(lambda l: l[i], new_mu))
            loss_rows.append(float(losses[i]))

    out: List[Tuple[ClientUpdate, float]] = []
    for i, (c, k, it) in enumerate(zip(clients, ks, snapshot_iters)):
        c.commit_cohort(mu_rows[i])
        if res_spec is not None:
            c.commit_residual(res_commits[i])
        upd = ClientUpdate(c.client_id, it, k, deltas_rows[i],
                           c.num_samples)
        out.append((upd, loss_rows[i]))
    return out
