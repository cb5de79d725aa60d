"""The Pallas kernel compiles for a TPU v5e at the source paper's sizes.

Interpret mode (every other kernel test) checks semantics only; Mosaic's
compiler is what refuses unaligned slices, unsupported primitives and
oversized VMEM. These tests run that compiler for a DESCRIBED v5e (no chip
needed): both kernel entries with interpret=False, at seismology-64k and
power-512k (`configs/natsa.py`) plus a long AB geometry, asserting the
program holds the Mosaic custom call and fits one chip's memory.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and a test-collection-time load would
make pytest-xdist workers disagree on what exists.
"""

import pytest

import jax
import jax.numpy as jnp

from repro.configs.natsa import PAPER_WORKLOADS
from repro.core.matrix_profile import default_exclusion
from repro.kernels import DEFAULT_DT, DEFAULT_IT, natsa_mp, ops

V5E_HBM_BYTES = 16 * 10**9     # one v5e chip
WORKLOADS = {w.name: w for w in PAPER_WORKLOADS}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("workload", ["seismology-64k", "power-512k"])
def test_self_join_kernel_compiles_for_v5e(one_chip, workload):
    wl = WORKLOADS[workload]
    it, dt = DEFAULT_IT, DEFAULT_DT
    l = wl.n - wl.window + 1
    excl = default_exclusion(wl.window)
    # the padded geometry `ops._pad_streams` hands the kernel
    n_rows, n_diags = -(-l // it), -(-(l - excl) // dt)
    lp = n_rows * it + excl + n_diags * dt
    col_tile = ops.auto_col_tile(lp, it, dt, None)

    def fn(df, dg, invn, cov0):
        return natsa_mp.rowmax_profile(df, dg, invn, cov0, it=it, dt=dt,
                                       excl=excl, l=l, col_tile=col_tile,
                                       interpret=False)

    stream = jax.ShapeDtypeStruct((lp,), jnp.float32, sharding=one_chip)
    seeds = jax.ShapeDtypeStruct((n_diags * dt,), jnp.float32,
                                 sharding=one_chip)
    _compile(fn, (stream, stream, stream, seeds))


@pytest.mark.parametrize("workload,n_a", [
    ("seismology-64k", None),       # square AB join at paper size
    ("power-512k", None),
    ("seismology-64k", 4096),       # long AB: a short query vs 512k
])
def test_ab_join_kernel_compiles_for_v5e(one_chip, workload, n_a):
    wl = WORKLOADS[workload]
    m = wl.window
    if n_a is None:
        n_a, n_b = wl.n, wl.n
    else:
        n_b = WORKLOADS["power-512k"].n
    it, dt = DEFAULT_IT, DEFAULT_DT
    # short side on rows (the planner's swap_ab), one span over the whole
    # signed diagonal space: the padded geometry `ops._pad_streams_ab` makes
    la, lb = sorted((n_a - m + 1, n_b - m + 1))
    s0, s1 = -(la - 1), lb
    n_rows, n_diags = -(-la // it), -(-(s1 - s0) // dt)
    jpad = -s0
    jlen = max(n_rows * it + s0 + n_diags * dt + jpad, jpad + lb)
    col_tile = ops.auto_col_tile(jlen, it, dt, None)

    def fn(df_i, dg_i, invn_i, df_j, dg_j, invn_j, cov0):
        return natsa_mp.rowmax_profile_ab(
            df_i, dg_i, invn_i, df_j, dg_j, invn_j, cov0, it=it, dt=dt,
            k_start=s0, k_end=s1, l_i=la, l_j=lb, jpad=jpad,
            col_tile=col_tile, interpret=False)

    def f32(n):
        return jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)

    rows, cols = f32(n_rows * it), f32(jlen)
    _compile(fn, (rows, rows, rows, cols, cols, cols, f32(n_diags * dt)))
