"""Per-kernel interpret-mode validation vs the pure-jnp oracles:
shape/dtype sweeps + hypothesis property checks (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="install .[test] extras for property tests")
from hypothesis import given, settings, strategies as st

# broad interpret-mode Pallas sweeps: full lane only (fast-lane coverage of
# every kernel lives in tests/kernels/test_dispatch.py)
pytestmark = pytest.mark.slow

from repro.kernels.adam import ops as adam_ops
from repro.kernels.adam.ref import ref_adam_update
from repro.kernels.e2afs_sqrt import ops as sqrt_ops
from repro.kernels.e2afs_sqrt.ref import ref_rsqrt, ref_sqrt
from repro.kernels.rmsnorm import ops as rms_ops
from repro.kernels.rmsnorm.ref import ref_rmsnorm
from repro.kernels.sobel import ops as sobel_ops
from repro.kernels.sobel.ref import ref_sobel

SHAPES = [(16,), (128,), (1000,), (8, 128), (3, 5, 7), (2, 256, 130)]
DTYPES = [jnp.float16, jnp.bfloat16, jnp.float32]


class TestE2AFSSqrtKernel:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_sqrt_matches_ref(self, shape, dtype):
        key = jax.random.key(hash((shape, str(dtype))) % 2**31)
        x = jnp.abs(jax.random.normal(key, shape, jnp.float32)) * 100 + 0.01
        x = x.astype(dtype)
        out = sqrt_ops.sqrt(x)
        ref = ref_sqrt(x)
        # identical integer datapath -> bit-exact
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_rsqrt_matches_ref(self, dtype):
        x = jnp.abs(jax.random.normal(jax.random.key(0), (4, 257), jnp.float32)) + 0.1
        x = x.astype(dtype)
        np.testing.assert_array_equal(
            np.asarray(sqrt_ops.rsqrt(x)), np.asarray(ref_rsqrt(x))
        )

    def test_specials(self):
        x = jnp.asarray([0.0, jnp.inf, jnp.nan, -4.0, 4.0], jnp.float32)
        out = np.asarray(sqrt_ops.sqrt(x))
        assert out[0] == 0.0 and np.isinf(out[1]) and np.isnan(out[2]) and np.isnan(out[3])
        assert out[4] == 2.0

    def test_rsqrt_specials_match_core_datapath(self):
        """Zeros and subnormals of either sign, infinities, NaN: the kernel
        takes the core datapath's flush-to-zero rsqrt policy."""
        x = jnp.asarray([0.0, -0.0, 1e-40, -1e-40, 1e-39, jnp.inf, -jnp.inf,
                         jnp.nan, 4.0], jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(sqrt_ops.rsqrt(x)), np.asarray(ref_rsqrt(x))
        )


class TestRMSNormKernel:
    @pytest.mark.parametrize("rows,d", [(4, 128), (16, 512), (7, 384), (1, 2048)])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_matches_ref(self, rows, d, dtype):
        key = jax.random.key(rows * d)
        x = (jax.random.normal(key, (rows, d), jnp.float32) * 3).astype(dtype)
        scale = jax.random.normal(jax.random.key(1), (d,), jnp.float32) * 0.1
        out = rms_ops.rmsnorm(x, scale)
        ref = ref_rmsnorm(x, scale)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
        )

    def test_batched_shape(self):
        x = jax.random.normal(jax.random.key(0), (2, 3, 256), jnp.float32)
        scale = jnp.zeros((256,))
        assert rms_ops.rmsnorm(x, scale).shape == (2, 3, 256)


class TestAdamKernel:
    @pytest.mark.parametrize("shape", [(128,), (1000,), (64, 65)])
    def test_matches_ref(self, shape):
        key = jax.random.key(7)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        p = jax.random.normal(k1, shape, jnp.float32)
        g = jax.random.normal(k2, shape, jnp.float32)
        m = jax.random.normal(k3, shape, jnp.float32) * 0.1
        v = jnp.abs(jax.random.normal(k4, shape, jnp.float32)) * 0.01
        kw = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.1, b1c=0.5, b2c=0.25)
        po, mo, vo = adam_ops.adam_update(p, g, m, v, **kw)
        pr, mr, vr = ref_adam_update(p, g, m, v, **kw)
        np.testing.assert_allclose(np.asarray(po), np.asarray(pr), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(mo), np.asarray(mr), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vo), np.asarray(vr), rtol=1e-6, atol=1e-6)


class TestSobelKernel:
    @pytest.mark.parametrize("h,w", [(66, 130), (64, 64), (100, 80)])
    def test_matches_ref(self, h, w):
        img = jax.random.uniform(jax.random.key(h * w), (h, w), jnp.float32) * 255
        out = sobel_ops.sobel_magnitude(img)
        ref = ref_sobel(img)
        assert out.shape == (h - 2, w - 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-4)


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=3000),
    scale=st.floats(min_value=0.01, max_value=1000.0),
)
def test_property_kernel_equals_core_datapath(n, scale):
    """The kernel is the core datapath: bit-exact on any size/scale."""
    x = jnp.abs(jax.random.normal(jax.random.key(n), (n,), jnp.float32)) * scale + 1e-6
    np.testing.assert_array_equal(
        np.asarray(sqrt_ops.sqrt(x)), np.asarray(ref_sqrt(x))
    )