"""Compiles for a described TPU v5e, with no chip attached: the main path's
Pallas kernels at real widths, at the tiles the roofline prior picks for
that chip, and one qwen3-4b decode chunk at published widths, which must
fit one chip's HBM, never copy its KV cache out to the query heads and
never copy a layer's plane out of the stacked cache.
Nothing runs; a compile the chip's compiler refuses fails here, at no chip
time.

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


QWEN3_4B_SLOTS, QWEN3_4B_LINES = 12, 2048  # the benchmark's slot pool


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


def test_decode_attention_compiles_at_qwen3_4b_widths(one_chip):
    """The blocked decode-attention kernel on qwen3-4b's stacked cache,
    (36 layers, 12 slots, 2048 lines, 8 kv heads, 128), at the t-block the
    prior picks for a v5e: Mosaic accepts it, and the kernel reads the
    stacked buffers as they lie, with no copy in front of it."""
    from repro.kernels.attention.ops import decode_attention

    L, b, t, h, kv, hd = 36, QWEN3_4B_SLOTS, QWEN3_4B_LINES, 32, 8, 128
    args = [_shape(one_chip, s, dt) for s, dt in (
        ((b, h, hd), jnp.bfloat16), ((L, b, t, kv, hd), jnp.bfloat16),
        ((L, b, t, kv, hd), jnp.bfloat16), ((b,), jnp.int32), ((), jnp.int32),
        ((b,), jnp.bool_))]
    hlo = jax.jit(
        lambda q, k, v, pos, layer, active: decode_attention(
            q, k, v, pos, None, None, layer, active, scale=hd**-0.5,
            interpret=False)
    ).lower(*args).compile().as_text()
    pool = f"bf16[{L},{b},{t},{kv},{hd}]"
    kernel = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(kernel) == 1 and kernel[0].count(pool) == 2, kernel
    assert not [line for line in hlo.splitlines() if " copy(" in line and pool in line]


@pytest.fixture(scope="module")
def qwen3_4b_decode_chunk(topo):
    """One Engine decode chunk (12 slots, 2048-token cache, 8 steps, health
    detectors on) at published widths, from lm.init's own abstract params,
    compiled once for the tests below, as a TPU builds it: the dispatch
    layer resolves the compiled backend (this host would pick interpret)
    with the described chip's constants, so decode attention takes the
    blocked kernel."""
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.core import hw_model
    from repro.kernels import dispatch
    from repro.models import lm

    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = get_config("qwen3-4b", sqrt_unit="e2afs")
    params, _ = lm.init(cfg, jax.random.key(0), abstract=True)
    pool = lm.init_pool_state(cfg, QWEN3_4B_SLOTS, QWEN3_4B_LINES, abstract=True)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _shape(one_chip, s.shape, s.dtype), tree)
    state = [pool[k] for k in ("cache", "tok", "pos", "active", "remaining", "keys")]
    step = jax.jit(
        lambda p, c, tok, pos, act, rem, keys: lm.decode_slots_scan(
            p, cfg, c, tok, pos, act, rem, 8, keys=keys, with_health=True),
        donate_argnums=(1, 2, 3, 4, 5),
    )
    kind = topo.devices[0].device_kind
    prev = dispatch.set_backend("compiled")
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(hw_model, "chip_for_backend",
                       lambda interpret: hw_model.INTERPRET_CPU if interpret
                       else hw_model.chip_for_kind(kind))
            compiled = step.lower(on_chip(params), *on_chip(state)).compile()
    finally:
        dispatch.set_backend(prev)
    return cfg, compiled


def test_qwen3_4b_decode_chunk_fits_one_chip(qwen3_4b_decode_chunk):
    """The bf16 serving params are what make the chunk fit (fp32 would need
    ~27 GB)."""
    _, compiled = qwen3_4b_decode_chunk
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB"


def test_qwen3_4b_decode_chunk_never_expands_the_cache(qwen3_4b_decode_chunk):
    """Decode attention contracts each kv head against its query group: the
    optimised program holds no copy of the cache repeated to the query
    heads, neither grouped (b, t, kv, g, hd) nor flat (b, t, h, hd)."""
    cfg, compiled = qwen3_4b_decode_chunk
    b, t, kv, hd = QWEN3_4B_SLOTS, QWEN3_4B_LINES, cfg.n_kv_heads, cfg.d_head
    g = cfg.n_heads // kv
    hlo = compiled.as_text()
    for shape in ((b, t, kv, g, hd), (b, t, kv * g, hd)):
        dims = "[" + ",".join(map(str, shape)) + "]"
        assert dims not in hlo, f"cache expanded to {dims}"


def test_qwen3_4b_decode_chunk_never_copies_a_layer_plane(qwen3_4b_decode_chunk):
    """Decode attention reads each layer's K and V where they lie in the
    stacked (L, b, t, kv, hd) pool: the chunk holds no buffer of one
    plane's b·t·kv·hd elements (the inline path relays each plane out to
    (b, kv, t, hd), four such buffers a layer), and its attention is a
    kernel whose op name keeps the ``decode_attention`` scope, so the
    benchmark's scope reader still finds it."""
    import re

    cfg, compiled = qwen3_4b_decode_chunk
    b, t, kv, hd = QWEN3_4B_SLOTS, QWEN3_4B_LINES, cfg.n_kv_heads, cfg.d_head
    hlo = compiled.as_text()
    plane = b * t * kv * hd
    shapes = {tuple(int(d) for d in m.split(","))
              for m in re.findall(r"\[([0-9,]+)\]", hlo)}
    planes = [s for s in shapes if len(s) > 1 and np_prod(s) == plane]
    assert not planes, f"layer-plane buffers {planes}"
    pool = f"bf16[{cfg.n_layers},{b},{t},{kv},{hd}]"
    kernels = [line for line in hlo.splitlines()
               if "tpu_custom_call" in line and "decode_attention" in line
               and "pallas_call" in line]
    assert len(kernels) == 1, kernels
    assert kernels[0].count(pool) == 2  # K and V, as they lie in the carry


def np_prod(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def test_qwen3_4b_decode_attention_temporaries_under_one_layer_cache(one_chip):
    """One layer's decode attention over the 12-slot pool needs less scratch
    than that layer's K and V: it reads the cache where it lies."""
    from repro.configs import get_config
    from repro.layers import attention as attn

    cfg = get_config("qwen3-4b", sqrt_unit="e2afs")
    b, t, d = QWEN3_4B_SLOTS, QWEN3_4B_LINES, cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    bf16 = jnp.bfloat16
    p = {"wq": ((d, h, hd), bf16), "wk": ((d, kv, hd), bf16),
         "wv": ((d, kv, hd), bf16), "wo": ((h, hd, d), bf16),
         "q_norm": ((hd,), bf16), "k_norm": ((hd,), bf16)}
    p = {name: _shape(one_chip, s, dt) for name, (s, dt) in p.items()}
    cache = {name: _shape(one_chip, (b, t, kv, hd), bf16) for name in ("k", "v")}
    x = _shape(one_chip, (b, 1, d), bf16)
    pos = _shape(one_chip, (b,), jnp.int32)
    compiled = jax.jit(
        lambda p, x, cache, pos: attn.attention_decode(p, cfg, x, cache, pos),
        donate_argnums=(2,),
    ).lower(p, x, cache, pos).compile()
    layer_cache = 2 * b * t * kv * hd * jnp.dtype(bf16).itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_cache, f"{temp / 1e6:.1f} MB of scratch"


@pytest.fixture(scope="module")
def model16(topo):
    """A (data=1, model=16) mesh on a described v5e:4x4, the model axis of
    the production serving mesh."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:4x4")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:4x4 topology can be described here: {e}")
    return Mesh(np.asarray(desc.devices).reshape(1, 16), ("data", "model"))


@pytest.mark.parametrize("b,sq,t", [(4, 1, 256), (1, 128, 128)], ids=["decode", "prefill"])
@pytest.mark.parametrize("name", ["starcoder2-15b", "qwen3-4b"])
def test_serving_scores_stay_sharded_on_model16(model16, name, b, sq, t):
    """Under serve rules on a 16-wide model axis, where neither kv heads nor
    the query group divide 16 (starcoder2-15b 4 x 12, qwen3-4b 8 x 4) but
    h does, the serving block keeps the scores split over the query heads:
    no fp32 tensor of the per-device program spans all h heads, flat (a
    dim of h) or grouped (dims kv, g side by side)."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs import get_config
    from repro.distributed.constraints import axis_rules, logical_to_spec
    from repro.distributed.sharding import divisible_spec, serve_rules
    from repro.layers import attention as attn

    cfg = get_config(name)
    rules = serve_rules(cfg, model16)
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head

    def arg(axes, shape, dtype=jnp.bfloat16):
        spec = divisible_spec(logical_to_spec(axes, rules), shape, model16)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(model16, spec))

    def block(q, k, v, mask):
        with axis_rules(model16, rules):
            return attn._fold_masked_attention(q, k, v, mask, hd**-0.5, None, None,
                                               jnp.bfloat16)

    cache = arg(("batch", None, "kv_heads", None), (b, t, kv, hd))
    hlo = jax.jit(block).lower(
        arg(("batch", "seq", "heads", None), (b, sq, h, hd)), cache, cache,
        jax.ShapeDtypeStruct((b, sq, t), jnp.float32,
                             sharding=NamedSharding(model16, PartitionSpec())),
    ).compile().as_text()
    whole = [s for s in set(re.findall(r"f32\[([0-9,]+)\]", hlo))
             if str(h) in s.split(",") or f"{kv},{h // kv}" in s]
    assert not whole, f"fp32 tensors over all {h} heads: {whole}"
