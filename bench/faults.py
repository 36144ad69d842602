"""Broken variants of the timed path, for the checks of ``correct``.

Each breaker is called with the server before the harness wraps it (see
``harness.run_cell(breaker=...)``), so the harness records what it handed
the program and the program does something else underneath. A sound
comparison must read every one of them as not correct:

* ``unchanged_state`` — each step returns the graph it was given (the
  batch is dropped inside the step);
* ``half_batch`` — each step applies only the first half of its batch;
* ``altered_answer`` — G-Ray's answer is altered where it is produced (the
  first query vertex of every result moves to the next vertex id);
* ``dropped_results`` — G-Ray keeps only the first of each row's results
  (every other is marked invalid);
* ``shrunk_pem`` — the PEM recompute set is cut down to the vertices the
  batch touched, not their whole communities;
* ``halved_threshold`` — PEM cuts its split tree at half the threshold
  ``c`` the DQN chose, so every community is smaller than it should be.

``bf16_rwr`` is the control: the program with every RWR sweep rounded to
bfloat16, the precision below the float32 the configuration states.
"""

from __future__ import annotations

from typing import Callable, Dict


def unchanged_state(server) -> None:
    from repro.core.graph import UpdateBatch

    step = server.step_packed

    def broken(g, upd, n_events, t_start=None):
        return step(g, UpdateBatch.empty(server.u_max), n_events, t_start)

    server.step_packed = broken


def half_batch(server) -> None:
    import jax.numpy as jnp

    step = server.step_packed

    def broken(g, upd, n_events, t_start=None):
        # the add lane holds k forward arcs then their k mirrors
        k = int(jnp.sum(upd.add_mask)) // 2
        keep = jnp.arange(upd.add_mask.shape[0])
        half = (keep < k // 2) | ((keep >= k) & (keep < k + k // 2))
        return step(g, upd._replace(add_mask=upd.add_mask & half),
                    n_events, t_start)

    server.step_packed = broken


def altered_answer(server) -> None:
    for bucket in server.engine.buckets.values():
        match = bucket.match

        def broken(g, *a, _match=match, **kw):
            res = _match(g, *a, **kw)
            m = res.matched
            return res._replace(matched=m.at[..., 0].set(
                (m[..., 0] + 1) % g.n_max))

        bucket.match = broken


def dropped_results(server) -> None:
    import jax.numpy as jnp

    for bucket in server.engine.buckets.values():
        match = bucket.match

        def broken(g, *a, _match=match, **kw):
            res = _match(g, *a, **kw)
            first = jnp.arange(res.valid.shape[-1]) == 0
            return res._replace(valid=res.valid & first,
                                exact=res.exact & first)

        bucket.match = broken


def shrunk_pem(server) -> None:
    import numpy as np

    pem = server.engine.pem
    recompute_mask = pem.recompute_mask

    def broken(g, updated):
        mask, frac = recompute_mask(g, updated)
        ids = np.asarray(updated)
        only = np.zeros_like(mask)
        only[ids[ids >= 0]] = True
        return mask & only, frac

    pem.recompute_mask = broken


def halved_threshold(server) -> None:
    pem = server.engine.pem
    communities = pem.communities

    def broken(g):
        c = pem.c
        pem.c = max(1, c // 2)
        try:
            return communities(g)
        finally:
            pem.c = c

    pem.communities = broken


def bf16_rwr(server=None) -> None:
    """Round every RWR sweep's combine to bfloat16 (process-wide; call
    before anything compiles)."""
    import importlib

    import jax.numpy as jnp

    # the module, not the ``rwr`` function ``repro.core`` re-exports
    rwr = importlib.import_module("repro.core.rwr")
    combine = rwr._combine

    def low(e, agg, c):
        out = combine(e, agg.astype(jnp.bfloat16).astype(jnp.float32), c)
        return out.astype(jnp.bfloat16).astype(jnp.float32)

    rwr._combine = low


FAULTS: Dict[str, Callable] = {
    "unchanged_state": unchanged_state,
    "half_batch": half_batch,
    "altered_answer": altered_answer,
    "dropped_results": dropped_results,
    "shrunk_pem": shrunk_pem,
    "halved_threshold": halved_threshold,
}
