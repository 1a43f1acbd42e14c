"""Compile the main-path kernels for a described TPU v5e, at qwen3-4b's
published widths, without a chip: the TPU compiler refuses here what
interpret mode accepts (block shapes off the (8, 128) tiling, VMEM over the
scoped limit, kernels XLA cannot partition). Nothing runs, so these tests
say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and a worker that fails to load
it skips these tests from the fixture instead of collecting different tests
than its peers. Keep every chip-compile test in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels import paged_attention as pa

# qwen3-4b: wq site (d_model 2560 -> 32 heads x 128), 36 layers, n = 1000;
# paged decode with GQA 32/8, head_dim 128, 16-token pages
L, N, D1, D2 = 36, 1000, 2560, 4096
H, K, DH, PS = 32, 8, 128, 16

HARNESS = {"fourierft": kops.fourier_deltaw_harness,
           "dct": kops.dct_deltaw_harness}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("method", sorted(HARNESS))
def test_stacked_deltaw_forward_compiles(one_chip, method):
    h = HARNESS[method]
    fwd = lambda c, e: h(c, e, D1, D2, 300.0)
    compiled = jax.jit(fwd).lower(_spec((L, N), jnp.float32, one_chip),
                                  _spec((2, N), jnp.int32, one_chip)
                                  ).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("method", sorted(HARNESS))
def test_stacked_deltaw_grad_compiles(one_chip, method):
    h = HARNESS[method]
    grad = jax.grad(lambda c, e, g: jnp.vdot(g, h(c, e, D1, D2, 300.0)))
    compiled = jax.jit(grad).lower(_spec((L, N), jnp.float32, one_chip),
                                   _spec((2, N), jnp.int32, one_chip),
                                   _spec((L, D1, D2), jnp.float32, one_chip)
                                   ).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("L,d1,d2", [
    (36, 2560, 1024),          # qwen3-4b wv (wq: the harness tests above)
    (12, 4096, 4096),          # yi-9b wq, one chip's quarter of 48 layers
])
def test_fourier_deltaw_stack_kernels_compile(one_chip, L, d1, d2):
    """The FourierFT kernels at the other stacks and widths the train cells
    run: the scratch bases carried over the layer axis and dc's resident
    (L, 1, n) output block fit the scoped VMEM limit."""
    from repro.kernels import fourier_deltaw as fk
    npad = 1024
    d1p, d2p = -(-d1 // fk.DEFAULT_BM) * fk.DEFAULT_BM, \
        -(-d2 // fk.DEFAULT_BN) * fk.DEFAULT_BN
    entry = _spec((1, npad), jnp.int32, one_chip)
    fwd = jax.jit(lambda c, u, v: fk.deltaw_pallas(c, u, v, d1, d2, 300.0)
                  ).lower(_spec((L, 1, npad), jnp.float32, one_chip),
                          entry, entry).compile()
    dc = jax.jit(lambda g, u, v: fk.dc_pallas(g, u, v, d1, d2, 300.0)
                 ).lower(_spec((L, d1p, d2p), jnp.float32, one_chip),
                         entry, entry).compile()
    assert _has_kernel(fwd) and _has_kernel(dc)


@pytest.mark.parametrize("W", [1, 5])
def test_paged_attention_compiles(one_chip, W):
    B, n_pages, pps = 4, 64, 16
    compiled = jax.jit(pa.paged_attention_pallas).lower(
        _spec((B, W, H, DH), jnp.bfloat16, one_chip),
        _spec((n_pages, PS, K, DH), jnp.bfloat16, one_chip),
        _spec((n_pages, PS, K, DH), jnp.bfloat16, one_chip),
        _spec((B, pps), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip)).compile()
    assert _has_kernel(compiled)


def test_deltaw_compiles_inside_a_four_chip_program(topo):
    """XLA cannot partition a Mosaic kernel: under a 1x4 (data x model)
    mesh the harness must run it per shard of the layer stack."""
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(model=4, devices=topo.devices)
    rep = NamedSharding(mesh, P())
    h = kops.fourier_deltaw_harness

    def step(c, e, g):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jax.grad(lambda cc: jnp.vdot(
                g, h(cc, e, D1, D2, 300.0)))(c)

    compiled = jax.jit(step).lower(
        _spec((L, N), jnp.float32, rep), _spec((2, N), jnp.int32, rep),
        _spec((L, D1, D2), jnp.float32,
              NamedSharding(mesh, P(None, None, "model")))).compile()
    assert _has_kernel(compiled)
