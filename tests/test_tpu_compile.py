"""Compile-only checks for a described TPU v5e chip, no chip attached.

The kernels and jitted steps on the query engine's main path, compiled
by the TPU compiler at deployment widths (``interpret=False`` everywhere:
on this CPU host ``interpret=None`` would pick interpret mode and prove
nothing).  What Mosaic refuses here would fail on the chip the same way.

The two fused traversal kernels are strict xfails carrying the
compiler's refusal: Mosaic has no lowering for their per-lane stack pop
(``take_along_axis``).  Whoever makes them lower flips these tests and
the ``auto`` routes in ``core/session.py`` together.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the one running
these loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bvh import BVH4, DEFAULT_CONFIG, num_nodes
from repro.core.types import Ray, Triangle

DEPTH = 10  # BVH4 depth of a 2^20-triangle scene
N_TRI = 1 << 20
DEVICE_BYTES = 16 * 10**9  # one v5e chip's HBM


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _bvh(sharding, depth, n):
    s = lambda shape, dt=jnp.float32: _shape(sharding, shape, dt)  # noqa: E731
    leaves = 4**depth
    return BVH4(s((num_nodes(depth, 4), 3)), s((num_nodes(depth, 4), 3)),
                s((leaves,), jnp.int32), Triangle(s((n, 3)), s((n, 3)),
                                                  s((n, 3))),
                s((leaves,), jnp.int32))


def _rays(sharding, r):
    s = lambda shape, dt=jnp.float32: _shape(sharding, shape, dt)  # noqa: E731
    i = jnp.int32
    return Ray(s((r, 3)), s((r, 3)), s((r, 3)), s((r,)), s((r,), i),
               s((r,), i), s((r,), i), s((r, 3)))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("dim", [128, 100], ids=["sift128", "glove100"])
@pytest.mark.parametrize("metric", ["euclidean", "angular"])
def test_distance_kernel_compiles(one_chip, metric, dim):
    from repro.kernels import ops as kops

    kernel = {"euclidean": kops.euclidean_kernel,
              "angular": kops.angular_kernel}[metric]
    compiled = _compile(lambda q, c: kernel(q, c, interpret=False),
                        _shape(one_chip, (1024, dim)),
                        _shape(one_chip, (65536, dim)))
    assert "tpu_custom_call" in compiled.as_text()


def test_norms_kernel_compiles(one_chip):
    from repro.kernels.distance import norms_pallas

    compiled = _compile(
        lambda c: norms_pallas(c, bn=128, bk=128, interpret=False),
        _shape(one_chip, (65536, 128)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("ray_type", ["closest", "shadow"])
def test_wavefront_trace_compiles_within_hbm(one_chip, ray_type):
    from repro.core.wavefront import SHADOW_T_MIN, trace_wavefront

    t_min = SHADOW_T_MIN if ray_type == "shadow" else 0.0
    compiled = _compile(
        lambda b, r: trace_wavefront(b, r, DEPTH, ray_type=ray_type,
                                     t_min=t_min, config=DEFAULT_CONFIG),
        _bvh(one_chip, DEPTH, N_TRI), _rays(one_chip, 4096))
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < DEVICE_BYTES


MOSAIC_REFUSAL = ("Mosaic has no lowering for the per-lane stack-pop "
                  "take_along_axis (_gather_lowering_rule asserts "
                  "indices_aval.shape == in_aval.shape + (1,))")


@pytest.mark.xfail(strict=True, raises=AssertionError, reason=MOSAIC_REFUSAL)
def test_fused_traversal_kernel_compiles(one_chip):
    from repro.kernels.traverse import pack_bvh, traverse_packed

    _compile(lambda b, r: traverse_packed(
        pack_bvh(b, DEFAULT_CONFIG), r, 6, ray_type="closest", t_min=0.0,
        max_rounds=None, interpret=False, config=DEFAULT_CONFIG),
        _bvh(one_chip, 6, 4096), _rays(one_chip, 4096))


@pytest.mark.xfail(strict=True, raises=AssertionError, reason=MOSAIC_REFUSAL)
def test_fused_neighbor_kernel_compiles(one_chip):
    from repro.kernels.traverse import neighbor_packed, pack_point_bvh

    _compile(lambda b, r: neighbor_packed(
        pack_point_bvh(b), r, 6, 16, mode="nearest", interpret=False),
        _bvh(one_chip, 6, 4096), _rays(one_chip, 4096))
