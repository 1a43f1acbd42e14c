"""Pallas kernel validation: shape/dtype sweeps against the ref.py pure-jnp
(ifft2) oracle in interpret mode, forward and VJP, through the kernel
registry's backend selection (DESIGN.md §Kernels)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.fourierft import sample_entries
from repro.kernels import api, ops, ref


SHAPES = [
    (128, 128, 16),      # tile-aligned square
    (256, 512, 100),     # tile-aligned rectangular
    (300, 520, 64),      # ragged both dims
    (768, 768, 1000),    # paper's RoBERTa-base grid
    (512, 96, 37),       # ragged cols, odd n
    (64, 2048, 128),     # wide
]


@pytest.mark.parametrize("d1,d2,n", SHAPES)
def test_deltaw_kernel_vs_oracle(d1, d2, n):
    E = sample_entries(d1, d2, n, seed=7)
    c = jax.random.normal(jax.random.PRNGKey(1), (n,))
    r = ref.deltaw_ref(c, E, d1, d2, 300.0)
    k = ops.fourier_deltaw(c, E, d1, d2, 300.0, backend="interpret")
    np.testing.assert_allclose(k, r, atol=2e-4)


@pytest.mark.parametrize("d1,d2,n", SHAPES[:4])
def test_dc_kernel_vjp_vs_oracle(d1, d2, n):
    E = sample_entries(d1, d2, n, seed=7)
    c = jax.random.normal(jax.random.PRNGKey(1), (n,))
    g = jax.random.normal(jax.random.PRNGKey(2), (d1, d2))
    f = lambda c: jnp.vdot(g, ops.fourier_deltaw(c, E, d1, d2, 300.0,
                                                 backend="interpret"))
    dc = jax.grad(f)(c)
    np.testing.assert_allclose(dc, ref.dc_ref(g, E, 300.0), atol=2e-3,
                               rtol=1e-4)


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_deltaw_out_dtypes(out_dtype):
    d1, d2, n = 256, 256, 64
    E = sample_entries(d1, d2, n, seed=5)
    c = jax.random.normal(jax.random.PRNGKey(0), (n,))
    k = ops.fourier_deltaw(c, E, d1, d2, 10.0, backend="interpret",
                           out_dtype=out_dtype)
    assert k.dtype == out_dtype
    r = ref.deltaw_ref(c, E, d1, d2, 10.0)
    np.testing.assert_allclose(np.asarray(k, np.float32), r,
                               atol=(2e-4 if out_dtype == jnp.float32 else 2e-2))


def test_deltaw_stacked_vmap():
    d1, d2, n, L = 300, 520, 100, 4
    E = sample_entries(d1, d2, n, seed=7)
    cs = jax.random.normal(jax.random.PRNGKey(3), (L, n))
    ks = ops.fourier_deltaw(cs, E, d1, d2, 300.0, backend="interpret")
    es = ops.fourier_deltaw(cs, E, d1, d2, 300.0, backend="einsum")
    assert ks.shape == (L, d1, d2)
    np.testing.assert_allclose(ks, es, atol=2e-4)


def test_dc_kernel_stacked_vs_einsum():
    """The coefficient gradient of a stack, a distinct cotangent per layer:
    each layer's dc lands in its own row of the resident output block."""
    d1, d2, n, L = 300, 520, 100, 3
    E = sample_entries(d1, d2, n, seed=7)
    cs = jax.random.normal(jax.random.PRNGKey(3), (L, n))
    g = jax.random.normal(jax.random.PRNGKey(4), (L, d1, d2)) \
        * jnp.arange(1.0, L + 1.0)[:, None, None]

    def grad(mode):
        return jax.grad(lambda c: jnp.vdot(g, ops.fourier_deltaw(
            c, E, d1, d2, 300.0, backend=mode)))(cs)

    gk, ge = grad("interpret"), grad("einsum")
    assert gk.shape == (L, n)
    for layer in range(L):
        np.testing.assert_allclose(gk[layer], ge[layer], atol=2e-3,
                                   rtol=1e-4)


@pytest.mark.parametrize("direction", ["fwd", "dc"])
def test_stack_invariance_bitwise(direction):
    """Layer l of a stacked call is bitwise a one-layer call on c[l]: the
    bases built once per tile and reused over the stack change nothing."""
    d1, d2, n, L = 300, 520, 100, 3
    E = sample_entries(d1, d2, n, seed=11)
    cs = jax.random.normal(jax.random.PRNGKey(5), (L, n))
    g = jax.random.normal(jax.random.PRNGKey(6), (L, d1, d2))
    h = ops.fourier_deltaw_harness

    def run(c, gg):
        out, vjp = jax.vjp(lambda cc: h(cc, E, d1, d2, 300.0,
                                        interpret=True), c)
        return out if direction == "fwd" else vjp(gg)[0]

    stacked = np.asarray(run(cs, g))
    for layer in range(L):
        one = np.asarray(run(cs[layer:layer + 1], g[layer:layer + 1]))
        np.testing.assert_array_equal(stacked[layer], one[0])


def test_einsum_fallback_for_huge_dims():
    """dims over the int32 phase bound must resolve to the einsum backend
    even when the Pallas path is requested explicitly."""
    from repro.configs.base import PEFTConfig
    peft = PEFTConfig(method="fourierft", kernel_backend="interpret")
    assert api.resolve_op("deltaw", "fourierft", peft,
                          152064, 4096).backend == "einsum"
    assert api.resolve_op("deltaw", "fourierft", peft,
                          4096, 4096).backend == "interpret"
    # the DCT half-integer phase overflows earlier than the fourier phase
    assert api.resolve_op("deltaw", "dct", peft.replace(method="dct"),
                          40000, 128).backend == "einsum"
    assert api.resolve_op("deltaw", "fourierft", peft,
                          40000, 128).backend == "interpret"


def test_kernel_grad_matches_einsum_grad():
    d1, d2, n = 256, 384, 48
    E = sample_entries(d1, d2, n, seed=9)
    c = jax.random.normal(jax.random.PRNGKey(4), (n,))
    x = jax.random.normal(jax.random.PRNGKey(5), (3, d1))
    tgt = jax.random.normal(jax.random.PRNGKey(6), (3, d2))

    def loss(c, mode):
        dw = ops.fourier_deltaw(c, E, d1, d2, 50.0, backend=mode)
        return jnp.mean((x @ dw - tgt) ** 2)

    gk = jax.grad(lambda c: loss(c, "interpret"))(c)
    ge = jax.grad(lambda c: loss(c, "einsum"))(c)
    np.testing.assert_allclose(gk, ge, atol=1e-4, rtol=1e-3)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 8), st.integers(1, 8), st.integers(1, 200),
       st.integers(0, 1000))
def test_kernel_property_sweep(mh, mw, n, seed):
    """Hypothesis sweep over block-count space: kernel == oracle."""
    d1, d2 = 128 * mh, 128 * mw
    n = min(n, d1 * d2)
    E = sample_entries(d1, d2, n, seed=seed)
    c = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    k = ops.fourier_deltaw(c, E, d1, d2, 100.0, backend="interpret")
    r = ref.deltaw_ref(c, E, d1, d2, 100.0)
    np.testing.assert_allclose(k, r, atol=2e-4)
