"""The main path's scan programs compile for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached.  The shapes are those ``chip_smoke.py``
runs: the fig1-critical batch (k=1024, J=1e5, R=16), the SDSC-SP2 SRPT
batch (k=512, J=3000, R=32, queue_cap=160) and the four-chip
``jax-shard`` batch (R=64).  A compile that passes is not a chip run; what
these tests guard is that a later change still compiles for the chip
(f64 emulation refuses some ops, e.g. a bitcast to u64) and fits its
16 GB.  Every such compile lives in this one file: only the process
that describes the topology may load the TPU library.
"""

import os

import numpy as np
import pytest

import jax
from jax import enable_x64
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import shard, sim_batch
from repro.core.partition import balanced_partition
from repro.core.sim_jax import _srpt_args
from repro.core.workload import BatchTrace, figure1_workload
from repro.data.swf import sdsc_sp2_trace

#: bytes of device memory on one v5e chip
V5E_HBM = 16 * 10**9

K, J, R = 1024, 100_000, 16               # phase (a)
SK, SJ, SR, SQ = 512, 3_000, 32, 160      # phase (b)
MESH_R = 64                               # the four-chip batch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache, so it must not be written there."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_one_chip(compiled):
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert 0 < used < V5E_HBM, used
    return used


def test_fcfs_batch_compiles_for_v5e(one_chip, no_compile_cache):
    with enable_x64():
        f64 = _sds((R, J), np.float64, one_chip)
        compiled = sim_batch._fcfs_scan_batch.lower(
            f64, _sds((R, J), np.int32, one_chip), f64, k=K).compile()
    _fits_one_chip(compiled)


def test_bs_fcfs_batch_compiles_for_v5e(one_chip, no_compile_cache):
    part = balanced_partition(figure1_workload(K, theta=0.7))
    slots = np.asarray(part.slots, np.int32)
    with enable_x64():
        f64 = _sds((R, J), np.float64, one_chip)
        i32 = _sds((R, J), np.int32, one_chip)
        compiled = sim_batch._bs_scan_batch.lower(
            f64, i32, i32, f64, _sds(slots.shape, np.int32, one_chip),
            s_max=max(1, int(slots.max())), h=int(part.helpers),
            q_cap=min(J, 8192)).compile()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("sf", (False, True), ids=("ff-srpt", "sf-srpt"))
def test_srpt_batch_compiles_for_v5e(sf, one_chip, no_compile_cache):
    """The fast SRPT step sorts its f64 rank keys directly: the chip's f64
    emulation refuses the u64 bitcast an earlier version sorted."""
    batch = BatchTrace.from_trace(sdsc_sp2_trace(SJ, k=SK, load=0.85,
                                                 seed=0),
                                  SR, seed=0, method="block")
    NU = sim_batch._srpt_nu(batch)
    with enable_x64():
        f64 = _sds((SR, SJ), np.float64, one_chip)
        compiled = sim_batch._srpt_scan_batch.lower(
            f64, f64, f64, _sds((SR,), np.float64, one_chip),
            Q=_srpt_args(batch, SQ), NU=NU, sf=sf,
            k_mult=sim_batch._srpt_k_mult(NU, batch)).compile()
    _fits_one_chip(compiled)


def test_jax_shard_fcfs_compiles_for_four_chips(topo, no_compile_cache):
    mesh = Mesh(np.array(topo.devices), ("r",))
    lanes = NamedSharding(mesh, P("r"))
    with enable_x64():
        f64 = _sds((MESH_R, J), np.float64, lanes)
        compiled = shard._fcfs_shard_call.lower(
            f64, _sds((MESH_R, J), np.int32, lanes), f64, K,
            mesh).compile()
    # memory_analysis is per device: a quarter of the lanes (plus layout
    # padding) on each chip, not the whole batch on one
    per_dev = compiled.memory_analysis().argument_size_in_bytes
    quarter = MESH_R // 4 * J * (8 + 4 + 8)
    assert quarter <= per_dev < 2 * quarter
    _fits_one_chip(compiled)
