"""Fused decode-attention kernel: bit-parity against the inline decode
contract (fp32), documented bf16 tolerance, int8 scale folding, ring-wrap
validity, GQA grouping, batch-tile padding, dispatch registration, and the
``attention_decode(kernel=...)`` routing flag."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels.attention.ops import ref_decode_attention
from repro.layers import attention as attn
from repro.models.config import ModelConfig

B, T, H, KV, HD = 5, 16, 8, 2, 16
SCALE = HD**-0.5


def _inputs(dtype=jnp.float32, quantized=False):
    ks = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(ks[0], (B, H, HD), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, KV, HD), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, KV, HD), jnp.float32).astype(dtype)
    pos = jnp.asarray([0, 3, 7, 15, 20], jnp.int32)  # incl. past-the-end rows
    if not quantized:
        return q, k, v, pos, None, None
    k_scale = jnp.abs(jax.random.normal(ks[3], (B, T, KV))) * 0.1 + 0.01
    v_scale = jnp.abs(jax.random.normal(ks[4], (B, T, KV))) * 0.1 + 0.01
    return q, k, v, pos, k_scale, v_scale


def _run(args, **kw):
    return dispatch.dispatch("decode_attention", *args, scale=SCALE,
                             interpret=True, **kw)


class TestKernelParity:
    def test_fp32_bit_exact(self):
        q, k, v, pos, _, _ = _inputs()
        ref = ref_decode_attention(q, k, v, pos, scale=SCALE)
        np.testing.assert_array_equal(
            np.asarray(_run((q, k, v, pos))), np.asarray(ref)
        )

    def test_int8_scales_folded_bit_exact(self):
        args = _inputs(quantized=True)
        ref = ref_decode_attention(*args, scale=SCALE)
        np.testing.assert_array_equal(np.asarray(_run(args)), np.asarray(ref))

    def test_ring_wrap_validity(self):
        """wrap=True (sliding-window ring): rows with pos >= cache_len see
        every slot, rows below still mask the unwritten tail — and the
        kernel's in-VMEM mask matches the reference's exactly."""
        q, k, v, pos, _, _ = _inputs()
        ref = ref_decode_attention(q, k, v, pos, scale=SCALE, wrap=True)
        out = _run((q, k, v, pos), wrap=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        # masking is live: row 0 (pos=0) attends to one slot, so perturbing
        # a masked slot's K must not change its output
        k2 = k.at[:, 5].add(100.0)
        out2 = _run((q, k2, v, pos), wrap=True)
        np.testing.assert_array_equal(np.asarray(out2[0]), np.asarray(out[0]))
        assert (np.asarray(out2[3]) != np.asarray(out[3])).any()

    def test_bf16_tolerance(self):
        """bf16 activations: fp32 score/softmax chain keeps the paths within
        one bf16 ulp of each other (documented in docs/kernels.md)."""
        q, k, v, pos, _, _ = _inputs(jnp.bfloat16)
        ref = ref_decode_attention(q, k, v, pos, scale=SCALE)
        out = _run((q, k, v, pos))
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=1e-2, atol=1e-2,
        )

    def test_gqa_grouping_vs_mha(self):
        """kv == h (no grouping, the g==1 kernel branch) on the cache
        expanded through the GQA repeat agrees with the grouped contraction
        on the kv-head cache to fp32 rounding: the two contract the same
        lines, in another order inside each dot product.  On the GQA input
        the kernel still equals its oracle bit for bit."""
        q, k, v, pos, _, _ = _inputs()
        kx = jnp.repeat(k, H // KV, axis=2)
        vx = jnp.repeat(v, H // KV, axis=2)
        out_gqa = _run((q, k, v, pos))
        out_mha = _run((q, kx, vx, pos))
        np.testing.assert_allclose(
            np.asarray(out_gqa), np.asarray(out_mha), rtol=1e-6, atol=1e-6
        )
        ref_gqa = ref_decode_attention(q, k, v, pos, scale=SCALE)
        np.testing.assert_array_equal(np.asarray(out_gqa), np.asarray(ref_gqa))

    @pytest.mark.parametrize("block", [(1,), (2,), (4,), (8,), (16,)])
    def test_batch_tiling_invariant(self, block):
        """Every tile size (including ones that pad b=5 up) is bit-identical
        — tiling is a pure perf knob."""
        args = _inputs(quantized=True)
        ref = ref_decode_attention(*args, scale=SCALE)
        out = _run(args, block=block)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


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


class TestAttentionDecodeRouting:
    """attention_decode(kernel=...) routes the scored-attention block through
    the Pallas kernel with bit-identical output to the inline path."""

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
        np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))
        for key in c0:
            np.testing.assert_array_equal(np.asarray(c0[key]), np.asarray(c1[key]))

    def test_cfg_decode_kernel_is_the_default_route(self):
        cfg, p, x = self._setup()
        pos = jnp.asarray(4, jnp.int32)  # scalar lock-step path
        cache = attn.init_kv_cache(cfg, 3, 12, jnp.float32)
        o0, _ = attn.attention_decode(p, cfg, x, cache, pos)
        o1, _ = attn.attention_decode(
            p, cfg.replace(decode_kernel="fused").validate(), x, cache, pos
        )
        np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))

    def test_unknown_route_rejected(self):
        cfg, p, x = self._setup()
        cache = attn.init_kv_cache(cfg, 3, 12, jnp.float32)
        with pytest.raises(ValueError, match="unknown decode kernel"):
            attn.attention_decode(p, cfg, x, cache, jnp.asarray([0, 0, 0]),
                                  kernel="flash")
        with pytest.raises(AssertionError):
            cfg.replace(decode_kernel="flash").validate()


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
