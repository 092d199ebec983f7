"""Compile-only checks of the main-path device programs for a TPU v5e.

Nothing here runs on a chip: each test lowers a program for a *described*
``v5e:2x2`` topology and compiles it with the TPU compiler, which refuses
what the chip would refuse (illegal Pallas block shapes, collectives the
TPU cannot lower, programs that do not fit).  Interpret-mode tests cannot
see any of that.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.  Keep these tests in this
one file so the worker that loads the library runs all of them.

Code that asks ``jax.devices()`` still sees the CPU here, so each test
compiles the jitted program itself with shapes placed on the described
devices; the sharded drain builds its mesh from ``jax.devices()``, which
its test steers to the described chips.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from repro.core import fleet
from repro.kernels.wastage.ops import oom_probe, wastage_eval
from repro.sched import admission

# Admission widths of the cluster replay: 8192 queued/resident lanes,
# 64 nodes, the 256-lane drain cap, ADMIT_GRID grid points, 4 segments.
B_ADM, K_ADM, G_ADM, N_ADM, R_ADM, Q_ADM = 8192, 4, 64, 64, 64, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _probe_args(B, K, T, sharding):
    f32 = jnp.float32
    return (_spec((B, K), f32, sharding), _spec((B, K), f32, sharding),
            _spec((B, T), f32, sharding), _spec((B,), jnp.int32, sharding))


# ------------------------------------------------------------ probe kernels
@pytest.mark.parametrize("fn", [oom_probe, wastage_eval],
                         ids=["oom_probe", "wastage_eval"])
@pytest.mark.parametrize("B,K,T", [(1024, 16, 4096), (8, 4, 64)])
def test_wastage_kernels_compile(one_chip, fn, B, K, T):
    compiled = fn.lower(*_probe_args(B, K, T, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_probe_many_pallas_compiles(one_chip):
    """Phase A of ``simulate_fleet_many`` on the TPU backend: two length
    buckets probed in one program, each through the Mosaic kernel."""
    groups = []
    for B, T in ((512, 512), (64, 4096)):
        starts, peaks, mems, lengths = _probe_args(B, 16, T, one_chip)
        groups.append((starts, peaks, mems, mems, lengths,
                       _spec((B,), jnp.float32, one_chip)))
    compiled = fleet._probe_many.lower(
        tuple(groups), _spec((), jnp.float32, one_chip), dt=1.0,
        backend="pallas").compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


# ------------------------------------------------------------ drain program
def _drain_args(shard_node, rep):
    """Operands of the drain program; ``shard_node`` places the node-axis
    operands, ``rep`` everything else."""
    f64, i32, b = jnp.float64, jnp.int32, jnp.bool_
    return (
        _spec((B_ADM, K_ADM), f64, rep), _spec((B_ADM, K_ADM), f64, rep),
        _spec((B_ADM,), f64, rep), _spec((B_ADM,), f64, rep),
        _spec((B_ADM, G_ADM), f64, rep), _spec((B_ADM, G_ADM), f64, rep),
        _spec((N_ADM,), f64, shard_node), _spec((N_ADM,), b, shard_node),
        _spec((N_ADM, R_ADM), i32, shard_node),
        _spec((N_ADM, R_ADM), b, shard_node),
        _spec((N_ADM,), f64, shard_node),
        _spec((Q_ADM,), i32, rep), _spec((Q_ADM,), b, rep),
        _spec((), f64, rep), _spec((), f64, rep),
    )


def _packed_drain_args(sharding):
    """The unsharded drain's signature: the six lane buffers and the one
    packed float64 operand vector (``admission._drain_pack``'s layout,
    with the run's per-step operands for ``RUN_STEPS`` steps)."""
    S = admission.RUN_STEPS
    n_packed = (2 * N_ADM + 2 * N_ADM * R_ADM + S * N_ADM + 2 * Q_ADM
                + 2 * S + 2 * S * K_ADM + 3)
    return _drain_args(sharding, sharding)[:6] + (
        _spec((n_packed,), jnp.float64, sharding),)


@pytest.mark.parametrize("select", ["first", "headroom"])
def test_drain_compiles(one_chip, monkeypatch, select):
    monkeypatch.setattr(admission, "_KERNEL_CACHE", {})
    with jax.enable_x64(True):
        kernel = admission._drain_kernel(True, select)
        compiled = kernel.lower(*_packed_drain_args(one_chip), npad=N_ADM,
                                rmax=R_ADM, Q=Q_ADM).compile()
    # One int32 vector back (out_lane | out_node | out_step | count) and
    # admit_t.
    out, admit = compiled.out_info
    assert (out.shape, out.dtype) == ((3 * Q_ADM + 1,), jnp.int32)
    assert admit.shape == (B_ADM,)


@pytest.mark.parametrize("select", ["first", "headroom"])
def test_sharded_drain_compiles_on_2x2(topo, monkeypatch, select):
    """The node-sharded drain over all four chips of the described 2x2
    host, in float64 — the head-room rule included."""
    devices = list(topo.devices)
    assert len(devices) == 4
    monkeypatch.setattr(admission, "_KERNEL_CACHE", {})
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)
    mesh = Mesh(np.asarray(devices), ("nodes",))
    with jax.enable_x64(True):
        kernel = admission._drain_kernel_sharded(True, select, 4)
        compiled = kernel.lower(*_drain_args(
            NamedSharding(mesh, P("nodes")), NamedSharding(mesh, P()))
        ).compile()
    assert len(compiled.input_shardings[0][6].device_set) == 4
