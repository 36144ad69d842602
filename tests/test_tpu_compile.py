"""Compile-only checks of the ELL kernels and of the induced selection's
two programs for a TPU v5e that is described, not attached.

Mosaic is stricter than interpret mode (gather lowering, block tiling,
scoped VMEM), so these compile both kernels with the installed TPU
compiler at the paper width (``n_max`` 262,144, ``ell_width`` 64, RHS
widths 4 and 32 — the label-RWR table and the widest block ``ops.py``
issues at that ``n``) and at the reduced config's width, each reducing the
mirror's rows to its ``n`` vertices in the kernel; the selection's loop
over the paper-size graph and its compaction at a steady-size and a
catch-up-size bucket compile too. Nothing runs.
The topology is described inside a fixture, never at import, so every
xdist worker collects the same tests and only the one running this file
loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.spmv_ell.ops import resident_width
from repro.kernels.spmv_ell.spmv_ell import ell_vertex_maxima, ell_vertex_sums
from repro.sparse.ell import ell_row_capacity

PAPER = (262_144, 8_388_608, 64)   # configs/igpm_paper.py FULL
SMOKE = (1_024, 16_384, 16)        # configs/igpm_paper.py SMOKE


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler / topology support here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", ["partials", "maxima"])
@pytest.mark.parametrize("size,d", [(PAPER, 4), (PAPER, 32), (SMOKE, 4)],
                         ids=["paper-d4", "paper-d32", "smoke-d4"])
def test_ell_kernel_compiles_for_v5e(one_chip, kernel, size, d):
    n, e_max, k = size
    assert d <= resident_width(n, n)   # one launch carries the whole block
    r = ell_row_capacity(n, e_max, k)
    cols = _spec((r, k), jnp.int32, one_chip)
    mask = _spec((r, k), jnp.bool_, one_chip)
    row_ids = _spec((r,), jnp.int32, one_chip)
    x = _spec((n, d), jnp.float32, one_chip)
    if kernel == "partials":
        fn = ell_vertex_sums
        args = (cols, _spec((r, k), jnp.float32, one_chip), mask, row_ids, x)
    else:
        fn = ell_vertex_maxima
        args = (cols, mask, row_ids, x)
    compiled = jax.jit(fn, static_argnames=("n",)).lower(*args, n=n).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text   # Mosaic, not interpreted
    assert "scatter" not in text       # rows reduce to vertices in-kernel
    out = compiled.out_info
    assert out.shape == (n, d) and out.dtype == jnp.float32


@pytest.mark.parametrize("phase,n_cap,e_cap", [
    ("select", None, None), ("compact", 2_048, 8_192),
    ("compact", 32_768, 524_288)])
def test_induced_selection_compiles_for_v5e(one_chip, phase, n_cap, e_cap):
    from repro.core.subgraph import _compact, _select
    n, e_max, _ = PAPER
    if phase == "select":
        args = (_spec((e_max,), jnp.int32, one_chip),
                _spec((e_max,), jnp.int32, one_chip),
                _spec((e_max,), jnp.bool_, one_chip),
                _spec((), jnp.int32, one_chip),
                _spec((n,), jnp.bool_, one_chip))
        out = _select.lower(*args).compile().out_info
        assert [o.shape for o in out] == [(e_max + 65_536,)] * 2 + [()]
    else:
        pad = e_max + 65_536
        args = (_spec((pad,), jnp.int32, one_chip),
                _spec((pad,), jnp.int32, one_chip),
                _spec((), jnp.int32, one_chip),
                _spec((n,), jnp.int32, one_chip),
                _spec((n_cap,), jnp.int32, one_chip))
        out = _compact.lower(*args, e_cap=e_cap).compile().out_info
        assert out.senders.shape == (e_cap,)
        assert out.labels.shape == (n_cap,)
