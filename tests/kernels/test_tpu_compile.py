"""Compiles for a described TPU v5e, with no chip attached: the main path's
Pallas kernels at real widths, at the tiles the roofline prior picks for
that chip, and one qwen3-4b decode chunk at published widths, which must
fit one chip's HBM.  Nothing runs; a compile the chip's compiler refuses
fails here, at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library."""
import os

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

# the v5e compiler refuses a program that needs more ("Used 27.03G of 15.75G hbm")
V5E_HBM_BYTES = 15.75e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's compile cannot be read back from the persistent cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture()
def one_chip(topo, monkeypatch):
    """A single-device sharding on the described chip; the tile prior reads
    that chip's constants, as it would on the device."""
    from jax.sharding import SingleDeviceSharding

    from repro.core import hw_model

    kind = topo.devices[0].device_kind
    monkeypatch.setattr(
        hw_model, "chip_for_backend",
        lambda interpret: hw_model.INTERPRET_CPU if interpret
        else hw_model.chip_for_kind(kind),
    )
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_case(name):
    """(public wrapper call, argument shapes) for one kernel."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    if name == "rmsnorm":
        from repro.kernels.rmsnorm.ops import rmsnorm

        return (lambda x, s: rmsnorm(x, s, interpret=False),
                [((2048, 2560), bf16), ((2560,), f32)])
    if name in ("e2afs_sqrt", "e2afs_rsqrt"):
        from repro.kernels.e2afs_sqrt import ops

        fn = ops.sqrt if name == "e2afs_sqrt" else ops.rsqrt
        return (lambda x: fn(x, interpret=False), [((4096, 1024), f32)])
    if name == "kmeans_assign":
        from repro.kernels.kmeans.ops import kmeans_assign

        return (lambda p, c: kmeans_assign(p, c, interpret=False),
                [((1 << 20, 3), f32), ((20, 3), f32)])
    if name == "sobel":
        from repro.kernels.sobel.ops import sobel_magnitude

        return (lambda img: sobel_magnitude(img, interpret=False), [((512, 512), f32)])
    if name == "adam":
        from repro.kernels.adam.ops import adam_update

        # fp32 master weights and moments of one qwen3-4b MLP matrix
        return (lambda p, g, m, v: adam_update(p, g, m, v, lr=1e-3, b1c=0.5,
                                               b2c=0.5, interpret=False),
                [((2560, 9728), f32)] * 4)
    raise ValueError(name)


@pytest.mark.parametrize(
    "name", ["rmsnorm", "e2afs_sqrt", "e2afs_rsqrt", "kmeans_assign", "sobel", "adam"]
)
def test_kernel_compiles_at_prior_tile(one_chip, name):
    """Traced through its public wrapper, the kernel takes the tile the
    roofline prior picks for a v5e; Mosaic must accept it."""
    fn, shapes = _kernel_case(name)
    args = [_shape(one_chip, s, dt) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(
    strict=True,
    reason="Mosaic refuses the fused decode-attention kernel: 'tpu.matmul: Up "
    "to 1 batch dim supported' for its 4-D einsums, and 'Expected matmul acc "
    "to be 32-bit' in bf16",
)
def test_decode_attention_compiles_at_qwen3_4b_widths(one_chip):
    from repro.kernels.attention.ops import decode_attention

    b, t, h, kv, hd = 8, 2048, 32, 8, 128
    args = [_shape(one_chip, s, dt) for s, dt in (
        ((b, h, hd), jnp.bfloat16), ((b, t, kv, hd), jnp.bfloat16),
        ((b, t, kv, hd), jnp.bfloat16), ((b,), jnp.int32))]
    jax.jit(
        lambda q, k, v, pos: decode_attention(q, k, v, pos, scale=hd**-0.5,
                                              interpret=False)
    ).lower(*args).compile()


def test_qwen3_4b_decode_chunk_fits_one_chip(one_chip):
    """One Engine decode chunk (8 slots, 2048-token cache, 8 steps, health
    detectors on) at published widths, from lm.init's own abstract params:
    the bf16 serving params are what make it fit (fp32 would need ~27 GB)."""
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("qwen3-4b", sqrt_unit="e2afs")
    params, _ = lm.init(cfg, jax.random.key(0), abstract=True)
    pool = lm.init_pool_state(cfg, 8, 2048, abstract=True)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _shape(one_chip, s.shape, s.dtype), tree)
    state = [pool[k] for k in ("cache", "tok", "pos", "active", "remaining", "keys")]
    step = jax.jit(
        lambda p, c, tok, pos, act, rem, keys: lm.decode_slots_scan(
            p, cfg, c, tok, pos, act, rem, 8, keys=keys, with_health=True),
        donate_argnums=(1, 2, 3, 4, 5),
    )
    compiled = step.lower(on_chip(params), *on_chip(state)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB"
