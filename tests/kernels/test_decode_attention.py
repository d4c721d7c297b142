"""Blocked decode-attention kernel: bit-parity against its blocked jnp
oracle in interpret mode (fp32, bf16, int8 scales, ring wrap, every
t-block), the stacked cache read by layer index, rows with no live block,
lines past a row's end that are never used, the fetch plan and the
host-side block count, stated tolerances against the inline XLA block,
dispatch registration, and the routing of ``attention_decode``."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.constraints import axis_rules
from repro.kernels import dispatch
from repro.kernels.attention.attention import block_index, fetch_plan
from repro.kernels.attention.ops import blocks_fetched, ref_decode_attention
from repro.kernels.attention.ref import lines_per_block, row_blocks
from repro.layers import attention as attn
from repro.models.config import ModelConfig

B, T, H, KV, HD = 5, 16, 8, 2, 16
SCALE = HD**-0.5
# against the inline block (one softmax over the whole row) the kernel
# differs by rounding only: per-block rescaled exponentials, and weights
# that meet V before the sum is divided out.  fp32: a few fp32 ulps of the
# output; bf16: about one bf16 ulp (the inline path also rounds its scores
# to bf16 before the fp32 softmax, the kernel keeps them fp32).
TOL = {jnp.float32: dict(rtol=1e-5, atol=2e-6), jnp.bfloat16: dict(rtol=1e-2, atol=1e-2)}


def _inputs(dtype=jnp.float32, quantized=False, t=T, layers=None, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    lead = () if layers is None else (layers,)
    q = jax.random.normal(ks[0], (B, H, HD), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], lead + (B, t, KV, HD), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], lead + (B, t, KV, HD), jnp.float32).astype(dtype)
    pos = jnp.asarray([0, 3, 7, 15, 20], jnp.int32)  # incl. past-the-end rows
    if not quantized:
        return q, k, v, pos, None, None
    k_scale = jnp.abs(jax.random.normal(ks[3], lead + (B, t, KV))) * 0.1 + 0.01
    v_scale = jnp.abs(jax.random.normal(ks[4], lead + (B, t, KV))) * 0.1 + 0.01
    return q, k, v, pos, k_scale, v_scale


def _run(args, layer=None, active=None, **kw):
    return dispatch.dispatch("decode_attention", *args, layer, active, scale=SCALE,
                             interpret=True, **kw)


def _inline(q, k, v, pos, k_scale=None, v_scale=None, *, wrap=False):
    """The inline XLA block on the same cache, as attention_decode masks it."""
    t = k.shape[1]
    valid = jnp.arange(t)[None, :] <= pos[:, None]
    if wrap:
        valid = valid | (pos[:, None] >= t)
    mask = jnp.where(valid, 0.0, attn.NEG_INF)[:, None, :]
    return attn._fold_masked_attention(q[:, None], k, v, mask, SCALE, k_scale,
                                       v_scale, q.dtype)[:, 0]


class TestKernelParity:
    def test_fp32_bit_exact(self):
        q, k, v, pos, _, _ = _inputs()
        ref = ref_decode_attention(q, k, v, pos, scale=SCALE)
        np.testing.assert_array_equal(
            np.asarray(_run((q, k, v, pos, None, None))), np.asarray(ref)
        )

    def test_int8_scales_folded_bit_exact(self):
        args = _inputs(quantized=True)
        ref = ref_decode_attention(*args, scale=SCALE)
        np.testing.assert_array_equal(np.asarray(_run(args)), np.asarray(ref))

    def test_ring_wrap_validity(self):
        """wrap=True (sliding-window ring): rows with pos >= cache_len see
        every slot, rows below still mask the unwritten tail — and the
        kernel's in-VMEM mask matches the oracle's exactly."""
        q, k, v, pos, _, _ = _inputs()
        ref = ref_decode_attention(q, k, v, pos, scale=SCALE, wrap=True)
        out = _run((q, k, v, pos, None, None), wrap=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        # masking is live: row 0 (pos=0) attends to one slot, so perturbing
        # a masked slot's K must not change its output
        k2 = k.at[:, 5].add(100.0)
        out2 = _run((q, k2, v, pos, None, None), wrap=True)
        np.testing.assert_array_equal(np.asarray(out2[0]), np.asarray(out[0]))
        assert (np.asarray(out2[3]) != np.asarray(out[3])).any()
        # the wrapped row (pos 20 >= 16) reads all 16 lines, like the inline ring
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_inline(q, k, v, pos, wrap=True)),
            **TOL[jnp.float32],
        )

    def test_bf16_tolerance(self):
        """bf16 activations: the fp32 score/softmax chain keeps the kernel
        within one bf16 ulp of the inline block (docs/kernels.md)."""
        q, k, v, pos, _, _ = _inputs(jnp.bfloat16)
        ref = _inline(q, k, v, pos)
        out = _run((q, k, v, pos, None, None))
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=1e-2, atol=1e-2,
        )
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref_decode_attention(q, k, v, pos, scale=SCALE))
        )

    def test_gqa_grouping_vs_mha(self):
        """kv == h (no grouping, g == 1) on the cache expanded through the
        GQA repeat agrees with the grouped contraction on the kv-head cache
        to fp32 rounding: the two contract the same lines, in another order
        inside each dot product.  On the GQA input the kernel still equals
        its oracle bit for bit."""
        q, k, v, pos, _, _ = _inputs()
        kx = jnp.repeat(k, H // KV, axis=2)
        vx = jnp.repeat(v, H // KV, axis=2)
        out_gqa = _run((q, k, v, pos, None, None))
        out_mha = _run((q, kx, vx, pos, None, None))
        np.testing.assert_allclose(
            np.asarray(out_gqa), np.asarray(out_mha), rtol=1e-6, atol=1e-6
        )
        ref_gqa = ref_decode_attention(q, k, v, pos, scale=SCALE)
        np.testing.assert_array_equal(np.asarray(out_gqa), np.asarray(ref_gqa))

    @pytest.mark.parametrize("block", [(1,), (2,), (4,), (8,), (16,)])
    def test_batch_tiling_invariant(self, block):
        """Every requested t-block reads the cache in the blocks
        ``lines_per_block`` gives (a multiple of 8 dividing the cache, else
        the whole cache) and equals the oracle in those blocks bit for bit;
        the split moves the result by fp32 rounding only."""
        args = _inputs(quantized=True)
        lines = lines_per_block(T, block[0])
        assert T % lines == 0 and (lines % 8 == 0 or lines == T)
        out = _run(args, block=block)
        ref = ref_decode_attention(*args, scale=SCALE, lines=lines)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        whole = ref_decode_attention(*args, scale=SCALE, lines=T)
        np.testing.assert_allclose(np.asarray(out), np.asarray(whole), rtol=1e-5,
                                   atol=1e-7)

    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("layer", [0, 2])
    def test_stacked_layer_equals_per_layer_call(self, layer, quantized):
        """The stacked (L, b, t, kv, hd) operand with a layer index reads
        that layer's plane: bit for bit the per-layer 4-D call on it."""
        q, k, v, pos, ks, vs = _inputs(quantized=quantized, layers=3)
        out = _run((q, k, v, pos, ks, vs), layer=jnp.int32(layer), block=(8,))
        per = _run((q, k[layer], v[layer], pos,
                    None if ks is None else ks[layer],
                    None if vs is None else vs[layer]), block=(8,))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(per))
        other = _run((q, k[1], v[1], pos, None if ks is None else ks[1],
                      None if vs is None else vs[1]), block=(8,))
        assert not np.array_equal(np.asarray(out), np.asarray(other))

    @pytest.mark.parametrize("block", [(8,), (16,)])
    def test_rows_without_live_blocks_output_zeros(self, block):
        """A row that is not live reads no block and returns finite zeros;
        the live rows are bit for bit what they are with every row live."""
        args = _inputs()
        active = jnp.asarray([False, True, False, True, False])
        out = np.asarray(_run(args, active=active, block=block))
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[~np.asarray(active)], 0.0)
        full = np.asarray(_run(args, block=block))
        np.testing.assert_array_equal(out[np.asarray(active)], full[np.asarray(active)])
        ref = ref_decode_attention(*args, None, active, scale=SCALE,
                                   lines=lines_per_block(T, block[0]))
        np.testing.assert_array_equal(out, np.asarray(ref))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("block", [(8,), (16,)])
    def test_lines_past_row_end_are_not_read(self, block, dtype):
        """Lines past a row's position hold NaN — in the row's last block
        and in the blocks after it — and the output is bit-unchanged: such
        lines are selected away before they touch a score or an output, and
        the blocks past the row's end are never folded in."""
        q, k, v, pos, _, _ = _inputs(dtype, t=32)
        out = _run((q, k, v, pos, None, None), block=block)
        past = jnp.arange(32)[None, :] > pos[:, None]  # (b, t)
        nan_k = jnp.where(past[:, :, None, None], jnp.nan, k).astype(dtype)
        nan_v = jnp.where(past[:, :, None, None], jnp.nan, v).astype(dtype)
        out_nan = _run((q, nan_k, nan_v, pos, None, None), block=block)
        assert np.isfinite(np.asarray(out_nan, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(out_nan), np.asarray(out))

    @pytest.mark.parametrize("lines", [8, 16, 32, 64])
    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_tolerance_against_inline(self, dtype, quantized, lines):
        """Within the stated tolerance (``TOL``) of the inline block, for
        every t-block, with and without int8 scales folded."""
        q, k, v, _, ks, vs = _inputs(dtype, quantized, t=64, seed=lines)
        pos = jnp.asarray([0, 5, 17, 40, 63], jnp.int32)
        out = _run((q, k, v, pos, ks, vs), block=(lines,))
        ref = _inline(q, k, v, pos, ks, vs)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), **TOL[dtype])


class TestFetchPlan:
    """Which K/V block each grid step names, and the host's count of them."""

    @staticmethod
    def _walk(nblk, n_all):
        """The blocks the pipeline fetches: one each time the (row, block)
        the K index map names changes from the step before."""
        nblk = jnp.asarray(nblk, jnp.int32)
        krow, kend = fetch_plan(nblk, n_all)
        steps = [tuple(int(a) for a in block_index(i, j, nblk, krow, kend))
                 for i in range(len(nblk)) for j in range(n_all)]
        fetched = [s for n, s in enumerate(steps) if n == 0 or s != steps[n - 1]]
        return steps, fetched

    @pytest.mark.parametrize("nblk", list(itertools.product([0, 1, 3], repeat=3)))
    def test_walk_fetches_each_live_block_once(self, nblk):
        steps, fetched = self._walk(nblk, 3)
        live = {(i, j) for i, n in enumerate(nblk) for j in range(n)}
        # every live block is fetched, once; a call with no live row
        # fetches the pipeline's first block and nothing else
        assert len(fetched) == len(set(fetched))
        assert set(fetched) == (live or {(0, 0)})
        for i, n in enumerate(nblk):  # a live row's steps name its own blocks
            if n:
                assert steps[i * 3:i * 3 + n] == [(i, j) for j in range(n)]

    @pytest.mark.parametrize("wrap", [False, True])
    def test_host_count_equals_walk(self, wrap):
        """``blocks_fetched`` (numpy, what the engine reports) counts the
        blocks the index maps name, for positions across several calls."""
        t, lines, b = 32, 8, 5
        rng = np.random.default_rng(3)
        pos = rng.integers(0, 48 if wrap else t, size=(b, 6))
        live = rng.random((b, 6)) < 0.5
        live[:, 4] = False  # a call with no live row
        fetched, whole = blocks_fetched(pos, live, t, lines, wrap)
        walked = 0
        for c in range(6):
            nblk = row_blocks(jnp.asarray(pos[:, c]), jnp.asarray(live[:, c]), t,
                              lines, wrap)
            walked += len(self._walk(nblk, t // lines)[1])
        assert fetched == walked
        assert whole == b * 6 * (t // lines)


class TestDispatchRegistration:
    def test_registered(self):
        assert "decode_attention" in dispatch.KNOWN
        assert "decode_attention" in dispatch.registered()
        spec = dispatch.get("decode_attention")
        assert tuple(spec.tiling.default) in tuple(spec.tiling.candidates)
        assert spec.tiling.geometry is not None

    def test_reference_backend_route(self):
        prev = dispatch.set_backend("reference")
        try:
            q, k, v, pos, _, _ = _inputs()
            out = dispatch.dispatch("decode_attention", q, k, v, pos, None,
                                    None, scale=SCALE)
            ref = ref_decode_attention(q, k, v, pos, scale=SCALE)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        finally:
            dispatch.set_backend(prev)

    def test_prior_block_is_batch_free(self):
        """The t-block depends on the cache's shape alone, so a row reads
        the same blocks whatever pool it sits in; qwen3-4b's 2048-line
        cache on a v5e takes 512-line blocks."""
        from repro.core import hw_model
        from repro.kernels import tuning

        spec = dispatch.get("decode_attention")
        picks = set()
        for b in (1, 4, 12, 64):
            args = (jax.ShapeDtypeStruct((b, 32, 128), jnp.bfloat16),
                    jax.ShapeDtypeStruct((36, b, 2048, 8, 128), jnp.bfloat16))
            geom = spec.tiling.geometry(args)
            picks.add(min(
                (c for c in spec.tiling.candidates
                 if tuning.predict_block_time(c, geom, hw_model.TPU_V5E)[2]),
                key=lambda c: tuning.predict_block_time(c, geom, hw_model.TPU_V5E)[0],
            ))
        assert picks == {(512,)}


class TestAttentionDecodeRouting:
    """attention_decode routes the scored attention through the blocked
    kernel or its oracle within the stated tolerance of the inline path,
    with bit-identical cache writes; left unset, the route follows the
    backend, the cache dtype and the mesh scope."""

    def _setup(self):
        cfg = ModelConfig(
            name="t", n_layers=1, d_model=32, n_heads=H, n_kv_heads=KV,
            d_head=HD, d_ff=64, vocab=64, act_dtype="float32",
        ).validate()
        ks = jax.random.split(jax.random.key(1), 5)
        d = cfg.d_model
        p = {
            "wq": jax.random.normal(ks[0], (d, H, HD)) * 0.1,
            "wk": jax.random.normal(ks[1], (d, KV, HD)) * 0.1,
            "wv": jax.random.normal(ks[2], (d, KV, HD)) * 0.1,
            "wo": jax.random.normal(ks[3], (H, HD, d)) * 0.1,
        }
        x = jax.random.normal(ks[4], (3, 1, d), jnp.float32)
        return cfg, p, x

    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("route", ["fused", "reference"])
    def test_routes_match_inline(self, route, quantized):
        cfg, p, x = self._setup()
        pos = jnp.asarray([2, 5, 20], jnp.int32)
        cache = attn.init_kv_cache(cfg, 3, 12, jnp.float32, quantized=quantized)
        o0, c0 = attn.attention_decode(p, cfg, x, cache, pos, window=12)
        o1, c1 = attn.attention_decode(p, cfg, x, cache, pos, window=12,
                                       kernel=route)
        np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), **TOL[jnp.float32])
        for key in c0:
            np.testing.assert_array_equal(np.asarray(c0[key]), np.asarray(c1[key]))
        other = "reference" if route == "fused" else "fused"
        o2, _ = attn.attention_decode(p, cfg, x, cache, pos, window=12, kernel=other)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))

    def test_cfg_decode_kernel_is_the_default_route(self):
        cfg, p, x = self._setup()
        pos = jnp.asarray(4, jnp.int32)  # scalar lock-step path
        cache = attn.init_kv_cache(cfg, 3, 12, jnp.float32)
        o0, _ = attn.attention_decode(p, cfg, x, cache, pos)
        o1, _ = attn.attention_decode(
            p, cfg.replace(decode_kernel="fused").validate(), x, cache, pos
        )
        o2, _ = attn.attention_decode(p, cfg, x, cache, pos, kernel="fused")
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), **TOL[jnp.float32])

    def test_unknown_route_rejected(self):
        cfg, p, x = self._setup()
        cache = attn.init_kv_cache(cfg, 3, 12, jnp.float32)
        with pytest.raises(ValueError, match="unknown decode kernel"):
            attn.attention_decode(p, cfg, x, cache, jnp.asarray([0, 0, 0]),
                                  kernel="flash")
        with pytest.raises(AssertionError):
            cfg.replace(decode_kernel="flash").validate()

    @pytest.mark.parametrize("backend,dtype,mesh,want", [
        ("interpret", jnp.bfloat16, False, None),   # CPU: the parity suites
        ("compiled", jnp.bfloat16, False, "fused"),  # TPU, float cache
        ("compiled", jnp.float32, False, "fused"),
        ("compiled", jnp.int8, False, None),         # int8 folds inline
        ("compiled", jnp.bfloat16, True, None),      # Engine(mesh=)
        ("reference", jnp.bfloat16, False, None),
    ])
    def test_route_follows_backend_dtype_and_mesh(self, backend, dtype, mesh, want):
        """No option picks the route: the kernel serves on the compiled
        backend, for a float cache, outside a mesh rules scope; a forced
        ``decode_kernel`` wins everywhere."""
        cfg, _, _ = self._setup()
        prev = dispatch.set_backend(backend)
        try:
            scope = (axis_rules(jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                                                  ("data", "model")), {})
                     if mesh else _nullcontext())
            with scope:
                assert attn.decode_route(cfg, dtype) == want
                forced = cfg.replace(decode_kernel="reference").validate()
                assert attn.decode_route(forced, dtype) == "reference"
        finally:
            dispatch.set_backend(prev)


def _nullcontext():
    import contextlib

    return contextlib.nullcontext()


class TestGroupedContraction:
    """The serving block contracts each kv head against its query group
    instead of repeating the cache to h heads.  Query head ``j`` still sees
    kv head ``j // g`` — what the repeat assigned — and MHA (g == 1) keeps
    the plain einsum bit for bit.  On a mesh whose model axis divides h
    but neither kv nor g, the block keeps the flat layout, so the scores
    stay sharded (``test_tpu_compile.py``)."""

    KV, T = 2, 16

    def _inputs(self, g, sq, quantized):
        b, h = 3, self.KV * g
        ks = jax.random.split(jax.random.key(g * 10 + sq), 5)
        q = jax.random.normal(ks[0], (b, sq, h, HD), jnp.float32)
        k = jax.random.normal(ks[1], (b, self.T, self.KV, HD), jnp.float32)
        v = jax.random.normal(ks[2], (b, self.T, self.KV, HD), jnp.float32)
        k_scale = v_scale = None
        if quantized:
            k_scale = jnp.abs(jax.random.normal(ks[3], (b, self.T, self.KV))) * 0.1 + 0.01
            v_scale = jnp.abs(jax.random.normal(ks[4], (b, self.T, self.KV))) * 0.1 + 0.01
        # per-row validity, as slot-scheduled decode builds it
        pos = jnp.asarray([0, 7, 15])[:, None] + jnp.arange(sq)[None] - (sq - 1)
        mask = jnp.where(jnp.arange(self.T)[None, None] <= pos[:, :, None], 0.0,
                         attn.NEG_INF)
        return q, k, v, mask, k_scale, v_scale

    @staticmethod
    def _per_head(q, k, v, mask, k_scale, v_scale):
        """The block's maths one query head at a time over k[:, :, j // g]."""
        g = q.shape[2] // k.shape[2]
        outs = []
        for j in range(q.shape[2]):
            n = j // g
            sc = jnp.einsum("bsk,btk->bst", q[:, :, j], k[:, :, n]) * SCALE
            if k_scale is not None:
                sc = sc * k_scale[:, None, :, n]
            w = jax.nn.softmax(sc + mask, axis=-1)
            if v_scale is not None:
                w = w * v_scale[:, None, :, n]
            outs.append(jnp.einsum("bst,btk->bsk", w, v[:, :, n]))
        return jnp.stack(outs, axis=2)

    @pytest.mark.parametrize("quantized", [False, True])
    @pytest.mark.parametrize("sq", [1, 5])
    @pytest.mark.parametrize("g", [1, 4, 6])
    def test_block_matches_per_head_loop(self, g, sq, quantized):
        q, k, v, mask, k_scale, v_scale = self._inputs(g, sq, quantized)
        out = attn._fold_masked_attention(q, k, v, mask, SCALE, k_scale, v_scale,
                                          jnp.float32)
        assert out.shape == q.shape
        ref = self._per_head(q, k, v, mask, k_scale, v_scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("sq", [1, 5])
    @pytest.mark.parametrize("g", [1, 4, 6])
    def test_helpers_against_repeat(self, g, sq):
        """Against the repeat's ``_gqa_scores`` / ``_gqa_out``: MHA
        (g == 1) takes them unchanged, bit for bit; grouped scores and
        weighted sum agree within fp32 rounding.  (The grouped einsum at
        g == 1 would not be bit-equal: it sums in another order.)"""
        q, k, v, mask, _, _ = self._inputs(g, sq, False)
        b, _, h, _ = q.shape
        sc_rep = attn._gqa_scores(q, k)
        if g == 1:
            assert not attn._serve_grouped(h, self.KV)
            out = attn._fold_masked_attention(q, k, v, mask, SCALE, None, None,
                                              jnp.float32)
            w_rep = jax.nn.softmax(sc_rep * SCALE + mask[:, None], axis=-1)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(attn._gqa_out(w_rep, v)))
            return
        assert attn._serve_grouped(h, self.KV)
        sc = attn._grouped_scores(attn._group_queries(q, self.KV), k)
        assert sc.shape == (b, self.KV, g, sq, self.T)
        w = jax.nn.softmax(sc, axis=-1)
        out = attn._grouped_out(w, v)
        out_rep = attn._gqa_out(w.reshape(b, h, sq, self.T), v)
        np.testing.assert_allclose(np.asarray(sc.reshape(sc_rep.shape)),
                                   np.asarray(sc_rep), rtol=1e-6, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_rep),
                                   rtol=1e-6, atol=1e-6)
