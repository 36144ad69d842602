"""Public wrapper for the ELL SpMM Pallas kernels.

On the CPU backend the kernel body executes under ``interpret=True``;
on TPU the same call lowers to Mosaic. The kernels reduce rows to vertices
themselves; the wrapper chunks wide RHS batches so the VMEM-resident packed
X and output blocks stay within their budget.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.spmv_ell.spmv_ell import (LANES, ell_vertex_maxima,
                                             ell_vertex_sums, packed_bytes)
from repro.sparse.ell import EllGraph

# VMEM granted to the resident packed X and output blocks together (v5e:
# 128 MiB of VMEM per core, 16 MiB scoped by default — the kernel raises
# its own limit)
_VMEM_BUDGET = 64 << 20


def resident_width(n_x: int, n: int) -> int:
    """Columns of X one kernel launch carries: the widest pow-2 block
    (≤ 128 lanes) whose packed ``(n_x, d)`` X and ``(n, d)`` output fit
    the budget together. Every launch costs the same scalar work whatever
    its width, so wider blocks mean fewer launches."""
    d = LANES
    while d > 1 and packed_bytes(n_x, d) + packed_bytes(n, d) > _VMEM_BUDGET:
        d //= 2
    return d


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _col_pass(kernel, x: jnp.ndarray, n: int, *ell) -> jnp.ndarray:
    """Run a vertex kernel over X in resident-width column chunks. X has
    all ``n_max`` rows even when the ELL tile is one graph shard's row
    block (``n`` is then the shard's vertex count). Wide operands loop
    over their chunks with one kernel call site (``lax.map``): every site
    is a Mosaic compile, and full-graph G-Ray programs hold many sweeps."""
    interpret = _on_cpu()
    n_x, s = x.shape
    d_res = resident_width(n_x, n)
    if s <= d_res:
        return kernel(*ell, x, n, interpret=interpret)
    c = -(-s // d_res)
    xs = jnp.pad(x, ((0, 0), (0, c * d_res - s)))
    xs = xs.reshape(n_x, c, d_res).transpose(1, 0, 2)
    ys = jax.lax.map(lambda xc: kernel(*ell, xc, n, interpret=interpret), xs)
    return ys.transpose(1, 0, 2).reshape(n, c * d_res)[:, :s]


@partial(jax.jit, static_argnames=("n",))
def ell_spmm_kernel(cols: jnp.ndarray, vals: jnp.ndarray, mask: jnp.ndarray,
                    row_ids: jnp.ndarray, x: jnp.ndarray,
                    n: int) -> jnp.ndarray:
    """y = A_ell @ x; x: (n, d) → y: (n, d)."""
    return _col_pass(ell_vertex_sums, x, n, cols, vals, mask, row_ids)


def ell_spmm_graph(g: EllGraph, x: jnp.ndarray) -> jnp.ndarray:
    return ell_spmm_kernel(g.cols, g.vals, g.mask, g.row_ids, x, g.n)


@partial(jax.jit, static_argnames=("n",))
def ell_reach_kernel(cols: jnp.ndarray, mask: jnp.ndarray,
                     row_ids: jnp.ndarray, x: jnp.ndarray,
                     n: int) -> jnp.ndarray:
    """y[v] = max_{u in N(v)} x[u] for indicator x ∈ [0,1]: (n, d) → (n, d).

    The max-plus sibling of ``ell_spmm_kernel`` — one bounded-BFS frontier
    sweep on the ELL layout. Vertices with no live in-arcs get 0.
    """
    return _col_pass(ell_vertex_maxima, x, n, cols, mask, row_ids)


def ell_reach_graph(g: EllGraph, x: jnp.ndarray) -> jnp.ndarray:
    return ell_reach_kernel(g.cols, g.mask, g.row_ids, x, g.n)
