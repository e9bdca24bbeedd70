"""Set-up warm-up: every program shape the cell's traffic can reach, run
once through the program's own entry points on throwaway clients and a
throwaway server, so nothing compiles inside the window.

What the traffic can reach:

* local rounds of every K in ``[k_min, k_max]`` -- ``Client.run_local``
  (a fan-out of one client always takes it);
* with a cohort engine, a fan-out in every padded client bucket up to the
  number of clients (``cohort.run_cohort``, planned as the simulator
  plans it), at every K when K is fixed;
* with a drain window, a drain of every burst size ``B`` from 1 to the
  number of clients (``server.on_update_batch``).

Each of these runs from the seed's weights on real inputs -- the clients'
own mini-batches, and in the drains updates made from the seed
(``pb_reference.drain_updates``) -- and what it returns is kept for the
reference check: each local round's loss and the leaf norms of its
update, and each drain's change of the model. Since the window compiles
nothing (``compiles_in_window``), every local-round, fan-out and drain
program it runs is one of these. The real run's own first drains then
warm what is left (evaluation, the first aggregation and the model's flat
view).
"""
from __future__ import annotations

import contextlib
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

import pb_reference

#: threads that warm local rounds of different K at once
WORKERS = 8


def _bucket_representatives(n: int):
    """The smallest fan-out size of each padded client bucket up to ``n``."""
    out, b = [], 2
    while True:
        c = b // 2 + 1 if b > 2 else 2
        if c > n:
            return out
        out.append(c)
        b *= 2


def warm(task, fed, traffic: dict, weights, data, seed: int,
         plant=None) -> tuple:
    """Warm every reachable program. Returns the counts of what ran and
    the outputs the reference check compares: ``rounds``, one
    ``(client, k, loss, update leaf norms)`` per local round, and
    ``drains``, one ``(B, leaf norms of the model's change)`` per drain.
    ``plant`` breaks the throwaway server as the cell's fault breaks the
    real one."""
    from repro.core import budget, cohort
    from repro.core.client import Client
    from repro.core.server import ClientUpdate, make_server
    datasets = data[0]
    counts = {"rounds": 0, "fan_outs": 0, "drains": 0}
    rounds, drains = [], []

    # the matmul precision in force is thread-local: carry it over
    precision = jax.config.jax_default_matmul_precision

    def local_round(k):
        with (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext()):
            c = Client(0, task, datasets[0], fed, seed=0)
            upd, loss = c.run_local(weights, k, 1)
            return (0, k, loss, np.asarray(pb_reference.leaf_norms(
                upd.delta)))

    # rounds of different K are independent programs: load them from the
    # compile cache side by side
    ks = list(range(fed.k_min, fed.k_max + 1))
    with ThreadPoolExecutor(max_workers=min(WORKERS, len(ks))) as pool:
        rounds.extend(pool.map(local_round, ks))
    counts["rounds"] = len(ks)
    if fed.client_engine in cohort.COHORT_ENGINES:
        if fed.k_min != fed.k_max:
            raise ValueError("a cohort cell warms one K: set k_min == k_max")
        k = fed.k_min
        for size in _bucket_representatives(fed.num_clients):
            clients = [Client(i, task, datasets[i], fed, seed=0)
                       for i in range(size)]
            plan = budget.plan_cohort(
                task, fed, clients=size, k=k,
                param_bytes=sum(l.nbytes for l in jax.tree.leaves(weights)),
                prox_mu=0.0, ragged=False)
            if plan.engine != "loop":
                out = cohort.run_cohort(task, clients, [weights] * size,
                                        [k] * size, [1] * size,
                                        per_client_params=True,
                                        engine=plan.engine, plan=plan)
                rounds.extend(
                    (i, k, loss, np.asarray(pb_reference.leaf_norms(
                        upd.delta))) for i, (upd, loss) in enumerate(out))
            counts["fan_outs"] += 1
    if traffic["fed"].get("batch_window", 0.0) != 0.0:
        server = make_server("asyncfeded", weights, fed, backend="pallas")
        if plant is not None:
            plant(server)
        updates = pb_reference.drain_updates(weights, seed, fed.num_clients)
        for b in range(1, fed.num_clients + 1):
            before = server.params
            upds = [ClientUpdate(i, 1, fed.k_min, updates[i], 1)
                    for i in range(b)]
            replies = server.on_update_batch(upds)
            jax.block_until_ready([r.params for r in replies])
            drains.append((b, np.asarray(pb_reference.change_norms(
                server.params, before))))
            counts["drains"] += 1
        del server, updates
    return counts, {"rounds": rounds, "drains": drains}
