"""Compile the main path's device programs for a TPU v5e chip that is
described, not attached.

The TPU compiler is installed with jax, so it can build for a chip
topology without one: what Mosaic refuses here (block shapes off the
(8, 128) tiling, operand types the MXU does not take) it would refuse
on the chip. Interpret-mode kernel tests cannot see any of that. Each
case compiles one program at the widths the paper apps stream at and
checks that the kernel path really lowered to a Mosaic kernel
(``tpu_custom_call``). Nothing runs; results are covered by the
interpret-mode tests and ``chip_smoke.py`` on the chip.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.chip import compile_chip
from repro.chip.compile import stream_pipeline
from repro.core.crossbar_layer import MLPSpec, mlp_init
from repro.fleet.shard import _first_rows
from repro.kernels import ops
from repro.kernels.crossbar_mvm import crossbar_mvm
from repro.kernels.int8_matmul import int8_matmul

DEEP = (784, 200, 100, 10)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic_kernels(monkeypatch):
    """``ops`` picks interpret mode from the default backend, which is
    the CPU here; steer it to the Mosaic lowering the chip takes."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _deep_plan(system):
    spec = MLPSpec(DEEP, activation="threshold", out_activation="linear")
    return compile_chip(spec, params=mlp_init(jax.random.PRNGKey(0), spec),
                        system=system).plan


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("B,R,C,rows,cols", [
    (8, 7, 4, 128, 64),       # deep app layer 0 at the paper geometry
    (128, 7, 4, 128, 64),     # a full batch block
    (8, 7, 2, 128, 128),      # beyond-paper 128x128 geometry
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_crossbar_mvm_compiles_for_v5e(one_chip, B, R, C, rows, cols,
                                       dtype):
    f32 = jnp.float32
    hlo = _hlo(
        lambda x, gp, gn, s, b: crossbar_mvm(x, gp, gn, s, b,
                                             activation="threshold"),
        _sds(one_chip, (B, R, rows), dtype),
        _sds(one_chip, (R, C, rows, cols), f32),
        _sds(one_chip, (R, C, rows, cols), f32),
        _sds(one_chip, (R, C, cols), f32),
        _sds(one_chip, (C * cols,), f32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("B,K,N", [(8, 784, 200), (8, 3072, 100)])
@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("x_dtype", [jnp.int8, jnp.uint8])
def test_int8_matmul_compiles_for_v5e(one_chip, B, K, N, fused, x_dtype):
    x = _sds(one_chip, (B, K), x_dtype)
    w = _sds(one_chip, (K, N), jnp.int8)
    if fused:
        vec = _sds(one_chip, (N,), jnp.float32)
        hlo = _hlo(lambda x, w, s, o: int8_matmul(
            x, w, s, o, activation="threshold"), x, w, vec, vec)
    else:
        hlo = _hlo(int8_matmul, x, w)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("system", ["memristor", "digital"])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_deep_stream_step_compiles_for_v5e(one_chip, mosaic_kernels,
                                           system, use_kernel):
    """The jitted deep-app stream step, built from the shapes of a
    programmed plan's leaves."""
    plan = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                        _deep_plan(system))
    x = _sds(one_chip, (8, DEEP[0]), jnp.float32)
    hlo = _hlo(lambda p, x: stream_pipeline(p, x, use_kernel=use_kernel),
               plan, x)
    assert ("tpu_custom_call" in hlo) == use_kernel


@pytest.mark.parametrize("use_kernel", [False, True])
def test_deep_fleet_step_compiles_for_v5e_2x2(topo, mosaic_kernels,
                                             use_kernel):
    """The four-chip fleet step: the plan replicated and the batch
    sharded over a ``"chip"`` mesh of the host's four chips, one
    replica per chip (the program ``ShardedChip`` jits)."""
    mesh = Mesh(np.asarray(topo.devices), ("chip",))
    replicated = NamedSharding(mesh, P())
    plan = jax.tree.map(lambda a: _sds(replicated, a.shape, a.dtype),
                        _deep_plan("memristor"))
    x = _sds(NamedSharding(mesh, P("chip")), (4 * 8, DEEP[0]),
             jnp.float32)
    step = jax.shard_map(
        lambda p, xs: stream_pipeline(p, xs, use_kernel=use_kernel),
        mesh=mesh, in_specs=(P(), P("chip")), out_specs=P("chip"),
        check_vma=False)
    compiled = jax.jit(step).lower(plan, x).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel
    assert len(compiled.output_shardings.device_set) == 4


@pytest.mark.parametrize("rows", [4093, 1])
def test_ragged_cut_compiles_for_v5e_2x2(topo, rows):
    """``ShardedChip.stream`` on ragged rows: the four-chip step's
    output, padded to a multiple of four and sharded over the chips, is
    cut to the rows sent on the device; for an odd row count that
    leaves the result replicated on all four."""
    mesh = Mesh(np.asarray(topo.devices), ("chip",))
    padded = -(-rows // 4) * 4
    out = _sds(NamedSharding(mesh, P("chip")), (padded, DEEP[-1]),
               jnp.float32)
    compiled = _first_rows.lower(out, rows).compile()
    (sharding,) = jax.tree.leaves(compiled.output_shardings)
    assert len(sharding.device_set) == 4 and sharding.is_fully_replicated
