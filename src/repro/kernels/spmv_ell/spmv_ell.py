"""Pallas TPU kernels: ELL-padded SpMM and max-plus reach, reduced per vertex.

For every vertex v, over the padded ELL rows i it owns (``row_ids[i] == v``,
row-split spill rows included):

    sums[v, :]   = Σ_i Σ_k  mask[i,k] · vals[i,k] · X[cols[i,k], :]
    maxima[v, :] = max(0, max_i max_k  mask[i,k] ? X[cols[i,k], :] : 0)

The row → vertex reduction happens inside the kernel, so a sweep is one
launch per column chunk and no scatter: an XLA ``segment_sum`` over the
paper-size mirror's 393,216 rows costs the TPU compiler 16–20 s per call
site, and a full-graph G-Ray program holds two per unrolled step.

TPU mapping (DESIGN.md §2):
  * the grid walks the padded-row axis in order (one sequential
    "arbitrary" axis); each program's ``(BLOCK_ROWS, K + 2)`` tile of
    column ids (dead slots −1), plus each row's live width and owning
    vertex, and its tile of weights sit in SMEM, where the scalar unit
    reads one id at a time,
  * X rides in VMEM *lane-packed*: its columns are padded to ``dp`` (a
    power of two ≤ 128) and ``P = 128 / dp`` vertices share one 128-lane
    row, so the resident block costs ``n · dp · 4`` bytes instead of the
    ``n · 128 · 4`` a lane-padded ``(n, d)`` block would,
  * the gather is a scalar-addressed row read: vertex ``c`` lives in packed
    row ``c // P`` at lane offset ``(c % P) · dp``, and a lane rotation
    brings its ``dp`` values to lanes ``[0, dp)``. Only slot groups below
    the row's live width are visited, and rows with no live slot are
    skipped outright,
  * the output is a second resident packed block in the same layout; each
    live row rotates its result to its owner's lanes and folds it in
    (add or max) — rows are visited in row order, so a vertex's rows
    combine in the same order in every layout that keeps its rows in
    order (the graph-sharded row blocks of DESIGN.md §5 do).

The wrappers here take the plain ``(R, K)`` ELL arrays, the row owners and
the ``(n, d)`` dense operand; ``ops.py`` picks how many columns of X one
launch carries (:func:`resident_width`) and chunks wider operands.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 128            # padded ELL rows per grid program
_SUBLANES = 8
_DEFAULT_VMEM = 16 << 20    # Mosaic's default scoped-VMEM limit
_VMEM_MARGIN = 4 << 20      # Mosaic internal scratch


def packed_width(d: int) -> int:
    """Lanes one vertex takes in the packed X layout: pow-2 ≥ d."""
    if not 1 <= d <= LANES:
        raise ValueError(f"packed X carries 1..{LANES} columns, got {d}")
    return 1 << (d - 1).bit_length()


def packed_rows(n: int, dp: int) -> int:
    """Rows of a packed ``(rows, 128)`` block (sublane-aligned)."""
    per_row = LANES // dp
    return -(-(-(-n // per_row)) // _SUBLANES) * _SUBLANES


def packed_bytes(n: int, d: int) -> int:
    """VMEM bytes of a resident packed ``(n, d)`` f32 block."""
    return packed_rows(n, packed_width(d)) * LANES * 4


def _pack_x(x: jnp.ndarray) -> jnp.ndarray:
    n, d = x.shape
    dp = packed_width(d)
    rows = packed_rows(n, dp)
    xp = jnp.pad(x.astype(jnp.float32),
                 ((0, rows * (LANES // dp) - n), (0, dp - d)))
    return xp.reshape(rows, LANES)


def _row_of(x_ref, c, dp: int):
    """Vertex ``c``'s packed values, rotated to lanes ``[0, dp)``."""
    if dp == LANES:
        return x_ref[pl.ds(c, 1), :]
    shift = (LANES // dp).bit_length() - 1
    row = x_ref[pl.ds(c >> shift, 1), :]
    lane = (c & ((LANES // dp) - 1)) * dp
    return pltpu.roll(row, (LANES - lane) & (LANES - 1), 1)


def _unpack(yp: jnp.ndarray, n: int, d: int) -> jnp.ndarray:
    dp = packed_width(d)
    return yp.reshape(-1, dp)[:n, :d]


def _ell_operands(cols: jnp.ndarray, mask: jnp.ndarray,
                  row_ids: jnp.ndarray) -> jnp.ndarray:
    """SMEM operand: dead slots' ids set to −1, then two trailing columns —
    each row's live width (last live slot + 1) and its owning vertex; rows
    padded to the block with width 0."""
    r, k = cols.shape
    width = jnp.max(jnp.where(mask, jnp.arange(1, k + 1, dtype=jnp.int32), 0),
                    axis=1)
    ids = jnp.concatenate([jnp.where(mask, cols, -1).astype(jnp.int32),
                           width[:, None],
                           row_ids.astype(jnp.int32)[:, None]], axis=1)
    return jnp.pad(ids, ((0, (-r) % BLOCK_ROWS), (0, 0)))


def _group(k: int) -> int:
    return math.gcd(k, 8)


def _zero_fill(out_ref) -> None:
    """Zero the resident output block (first program only)."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        def fill(j, carry):
            out_ref[pl.ds(j * _SUBLANES, _SUBLANES), :] = jnp.zeros(
                (_SUBLANES, LANES), jnp.float32)
            return carry

        jax.lax.fori_loop(0, out_ref.shape[0] // _SUBLANES, fill, 0)


def _fold(out_ref, v, acc, dp: int, combine) -> None:
    """Fold a row's result (lanes ``[0, dp)`` of ``acc``) into vertex
    ``v``'s packed slot of the output block."""
    if dp == LANES:
        cur = out_ref[pl.ds(v, 1), :]
        out_ref[pl.ds(v, 1), :] = combine(cur, acc)
        return
    shift = (LANES // dp).bit_length() - 1
    lane0 = (v & ((LANES // dp) - 1)) * dp
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    mine = (lane >= lane0) & (lane < lane0 + dp)
    cur = out_ref[pl.ds(v >> shift, 1), :]
    out_ref[pl.ds(v >> shift, 1), :] = jnp.where(
        mine, combine(cur, pltpu.roll(acc, lane0, 1)), cur)


def _row_walk(ids_ref, out_ref, k: int, dp: int, slot, init: float,
              combine) -> None:
    """Visit this program's rows in order; each live row folds ``slot``
    over its live slot groups and combines the result into its owner."""
    g = _group(k)

    def row(i, carry):
        width = ids_ref[i, k]

        @pl.when(width > 0)
        def _():
            def slots(j, acc):
                for t in range(g):
                    acc = slot(i, j * g + t, acc)
                return acc

            acc = jax.lax.fori_loop(0, (width + (g - 1)) // g, slots,
                                    jnp.full((1, LANES), init, jnp.float32))
            _fold(out_ref, ids_ref[i, k + 1], acc, dp, combine)

        return carry

    jax.lax.fori_loop(0, BLOCK_ROWS, row, 0)


def _sums_kernel(ids_ref, w_ref, x_ref, out_ref, *, k: int, dp: int):
    _zero_fill(out_ref)

    def slot(i, s, acc):
        c = jnp.maximum(ids_ref[i, s], 0)
        return acc + w_ref[i, s] * _row_of(x_ref, c, dp)

    _row_walk(ids_ref, out_ref, k, dp, slot, 0.0, jnp.add)


def _maxima_kernel(ids_ref, x_ref, out_ref, *, k: int, dp: int):
    # the output starts at 0: vertices without a live in-arc read 0, and
    # dead slots inside a row's live width contribute 0 as masked slots do
    _zero_fill(out_ref)

    def slot(i, s, acc):
        c = ids_ref[i, s]
        v = _row_of(x_ref, jnp.maximum(c, 0), dp)
        return jnp.maximum(acc, jnp.where(c >= 0, v, 0.0))

    _row_walk(ids_ref, out_ref, k, dp, slot, -jnp.inf, jnp.maximum)


def _launch(kernel, operands, n_smem: int, k: int, x: jnp.ndarray, n: int,
            interpret: bool) -> jnp.ndarray:
    rp = operands[0].shape[0]
    d = x.shape[1]
    dp = packed_width(d)
    need = packed_bytes(x.shape[0], d) + packed_bytes(n, d) + _VMEM_MARGIN
    smem = [pl.BlockSpec((BLOCK_ROWS, a.shape[1]), lambda i: (i, 0),
                         memory_space=pltpu.SMEM) for a in operands[:n_smem]]
    out = pl.pallas_call(
        functools.partial(kernel, k=k, dp=dp),
        grid=(rp // BLOCK_ROWS,),
        in_specs=smem + [pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((packed_rows(n, dp), LANES),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need if need > _DEFAULT_VMEM else None),
        interpret=interpret,
    )(*operands, _pack_x(x))
    return _unpack(out, n, d).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def ell_vertex_sums(cols: jnp.ndarray, vals: jnp.ndarray, mask: jnp.ndarray,
                    row_ids: jnp.ndarray, x: jnp.ndarray, n: int,
                    interpret: bool = False) -> jnp.ndarray:
    """(R, K) ELL rows owned by ``n`` vertices × (n_x, d ≤ 128) dense →
    (n, d) weighted in-neighbour sums."""
    r, k = cols.shape
    ids = _ell_operands(cols, mask, row_ids)
    w = jnp.pad(jnp.where(mask, vals, 0.0).astype(jnp.float32),
                ((0, ids.shape[0] - r), (0, 0)))
    return _launch(_sums_kernel, (ids, w), 2, k, x, n, interpret)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def ell_vertex_maxima(cols: jnp.ndarray, mask: jnp.ndarray,
                      row_ids: jnp.ndarray, x: jnp.ndarray, n: int,
                      interpret: bool = False) -> jnp.ndarray:
    """(R, K) ELL rows owned by ``n`` vertices × (n_x, d ≤ 128) indicator
    matrix → (n, d) in-neighbour maxima, 0 where a vertex has none."""
    k = cols.shape[1]
    ids = _ell_operands(cols, mask, row_ids)
    return _launch(_maxima_kernel, (ids,), 1, k, x, n, interpret)
