"""The plain reference: the configured model's forward pass in float32.

Nothing here imports the program.  Widths come from the configuration file's
``model`` block, weights from ``bench/weights.py`` drawn again from the seed
one layer at a time, and every sqrt site takes the E2AFS-R reciprocal
square root re-derived below from its published description
(docs/numerics.md: a 4-region piecewise-linear datapath on the mantissa,
exponent halved by parity, slopes that are sums of two shifts).  Matrix
products run at ``Precision.HIGHEST``, so the TPU computes them in float32.

The sequences checked are teacher-forced: a prompt followed by the tokens
the program served for it, so position ``s - 1 + j`` of the reference
predicts served token ``j``.  Each sequence is padded at its end to a fixed
length (causal attention keeps padding out of the real positions), so a
handful of shapes compile once per checkout.

``low=True`` is the control: the same forward with every linear layer's
inputs (activations per row, weights per output column) rounded to fp8
e4m3 with a scale per row or column: the step down from the configuration's
bf16 that a faster path would take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

__all__ = ["rsqrt_e2afs", "Reference"]

HI = jax.lax.Precision.HIGHEST

# E2AFS-R: for x = 2^r (1 + Y), the result's mantissa is
# intercept - (Y >> a) - (Y >> b) on a Q10 grid rescaled to the format,
# chosen by (r odd, Y >= 1/2); even r gives exponent -r/2 - 1, odd r gives
# -(r + 1)/2, and a result below 1.0 is renormalised by one place.
_REGIONS = {(0, 0): (1, 2, 2030), (0, 1): (2, 3, 1835),
            (1, 0): (1, 8, 1428), (1, 1): (2, 4, 1336)}


def rsqrt_e2afs(x):
    """E2AFS-R rsqrt of positive normal float32 values (norm inputs carry
    an epsilon, so zero, subnormals and specials never reach it)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    exp = (bits >> 23) & 0xFF
    man = bits & 0x7FFFFF
    one = 1 << 23
    r = exp - 127
    odd = r & 1
    hi = man >> 22
    exp_out = jnp.where(odd == 1, -((r + 1) >> 1), -(r >> 1) - 1) + 127

    def region(o, h):
        a, b, c = _REGIONS[(o, h)]
        return c * (one // 1024) - (man >> a) - (man >> b)

    res = jnp.where(odd == 1, jnp.where(hi == 1, region(1, 1), region(1, 0)),
                    jnp.where(hi == 1, region(0, 1), region(0, 0)))
    under = res < one
    res = jnp.where(under, res << 1, res)
    exp_out = exp_out - under.astype(jnp.int32)
    out = (exp_out << 23) | ((res - one) & 0x7FFFFF)
    return jax.lax.bitcast_convert_type(out, jnp.float32)


def _fp8(x, axis):
    """Round to fp8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, low):
    """(n, i) @ (i, o) in float32, or through fp8 inputs for the control."""
    if low:
        a, w = _fp8(a, 1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, gain, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * rsqrt_e2afs(ms + eps) * (1.0 + gain)


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * rsqrt_e2afs(var + eps) * scale + bias


def _rope(x, pos, theta):
    """Rotate-half RoPE: x (p, heads, hd), pos (p,)."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


class Reference:
    """The forward pass of one configuration file's ``model`` block: the
    dense default of bench/archs/dense.py.  ``windows`` holds each layer's
    sliding window (None: full causal attention); here every layer takes
    the model block's ``sliding_window``."""

    Q_BLOCK = 512
    # weight rules consulted before bench/weights.py's (an architecture
    # module's ``RULES``); the program's draw reads the same
    RULES: dict = {}

    def __init__(self, model: dict, seed: int):
        self.m = model
        self.words = weights.seed_words(seed)
        self.d = model["hidden_size"]
        self.h = model["num_attention_heads"]
        self.kv = model["num_key_value_heads"]
        self.hd = model["head_dim"]
        self.f = model["intermediate_size"]
        self.vocab = model["vocab_size"]
        self.padded_vocab = -(-self.vocab // 256) * 256
        self.layers = model["num_hidden_layers"]
        self.rms = model["norm"] == "rmsnorm"
        self.eps = model["norm_eps"]
        self.windows = [model.get("sliding_window")] * self.layers
        self.theta = float(model["rope_theta"])
        self.bias = bool(model.get("use_bias", False))

    # -- weights, drawn again from the seed ---------------------------------

    def _layer_shapes(self) -> dict:
        d, h, kv, hd, f = self.d, self.h, self.kv, self.hd, self.f
        s = {"layers/attn/wq": (d, h, hd), "layers/attn/wk": (d, kv, hd),
             "layers/attn/wv": (d, kv, hd), "layers/attn/wo": (h, hd, d)}
        if self.m.get("qk_norm"):
            s.update({"layers/attn/q_norm": (hd,), "layers/attn/k_norm": (hd,)})
        if self.bias:
            s.update({"layers/attn/bq": (h, hd), "layers/attn/bk": (kv, hd),
                      "layers/attn/bv": (kv, hd), "layers/attn/bo": (d,)})
        if self.rms:
            s.update({"layers/ln1": (d,), "layers/ln2": (d,)})
        else:
            s.update({f"layers/{n}_{p}": (d,) for n in ("ln1", "ln2")
                      for p in ("scale", "bias")})
        if self.m["hidden_act"] == "silu":
            s.update({"layers/mlp/wi_gate": (d, f), "layers/mlp/wi_up": (d, f),
                      "layers/mlp/wo": (f, d)})
        else:
            s.update({"layers/mlp/wi_up": (d, f), "layers/mlp/bi": (f,),
                      "layers/mlp/bo": (d,), "layers/mlp/wo": (f, d)})
        return s

    @functools.partial(jax.jit, static_argnums=0)
    def _draw_layer(self, words, layer):
        w = weights.layer_leaves(words, self._layer_shapes(), layer, self.RULES)
        return {k.removeprefix("layers/"): v.astype(jnp.float32) for k, v in w.items()}

    def draw_layer(self, layer: int) -> dict:
        """{program leaf path within a layer (``attn/wq``): float32 array}
        of one layer, drawn again from the seed."""
        return self._draw_layer(self.words, jnp.int32(layer))

    def draw_top(self) -> dict:
        """The leaves outside the layers (``embed``, ``ln_f``, ...) in
        float32, with ``unembed`` as the (d, vocab) unembedding the forward
        uses (the embedding's transpose where they are tied)."""
        return self._draw_top(self.words)

    @functools.partial(jax.jit, static_argnums=0)
    def _draw_top(self, words):
        d, vp = self.d, self.padded_vocab
        s = {"embed": (vp, d)}
        if not self.m["tie_word_embeddings"]:
            s["unembed"] = (d, vp)
        s.update({"ln_f": (d,)} if self.rms else {"ln_f_scale": (d,), "ln_f_bias": (d,)})
        w = {p: weights.leaf(weights.key_of(words, p, -1), sh,
                             *weights.leaf_spec(p, sh, self.RULES))
             for p, sh in s.items()}
        out = {k: v.astype(jnp.float32) for k, v in w.items()}
        out["unembed"] = (out["embed"].T if self.m["tie_word_embeddings"]
                          else out["unembed"])[:, : self.vocab]
        return out

    # -- one layer over one padded sequence ---------------------------------

    def _norm(self, x, w, name):
        if self.rms:
            return _rms(x, w[name], self.eps)
        return _layernorm(x, w[f"{name}_scale"], w[f"{name}_bias"], self.eps)

    def _attend(self, q, k, v, pos, window):
        """Causal (and windowed) GQA over one sequence, in query blocks."""
        p = q.shape[0]
        g = self.h // self.kv
        qb = min(self.Q_BLOCK, p)
        q = q.reshape(p // qb, qb, self.kv, g, self.hd)
        qpos = pos.reshape(p // qb, qb)

        def block(args):
            qi, pi = args
            s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HI) * self.hd ** -0.5
            diff = pi[:, None] - pos[None, :]
            ok = diff >= 0
            if window:
                ok = ok & (diff < window)
            s = jnp.where(ok[None, None], s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("kgqt,tkd->qkgd", a, v, precision=HI)

        out = jax.lax.map(block, (q, qpos))
        return out.reshape(p, self.h * self.hd)

    @functools.partial(jax.jit, static_argnums=(0, 4, 5))
    def _layer(self, w, x, pos, low, window):
        d, h, kv, hd = self.d, self.h, self.kv, self.hd
        p = x.shape[0]
        a = self._norm(x, w, "ln1")
        q = _mm(a, w["attn/wq"].reshape(d, h * hd), low).reshape(p, h, hd)
        k = _mm(a, w["attn/wk"].reshape(d, kv * hd), low).reshape(p, kv, hd)
        v = _mm(a, w["attn/wv"].reshape(d, kv * hd), low).reshape(p, kv, hd)
        if self.bias:
            q, k, v = q + w["attn/bq"], k + w["attn/bk"], v + w["attn/bv"]
        if self.m.get("qk_norm"):
            q = _rms(q, w["attn/q_norm"], self.eps)
            k = _rms(k, w["attn/k_norm"], self.eps)
        q, k = _rope(q, pos, self.theta), _rope(k, pos, self.theta)
        x = x + _mm(self._attend(q, k, v, pos, window), w["attn/wo"].reshape(h * hd, d), low)
        if self.bias:
            x = x + w["attn/bo"]
        a = self._norm(x, w, "ln2")
        if self.m["hidden_act"] == "silu":
            m = jax.nn.silu(_mm(a, w["mlp/wi_gate"], low)) * _mm(a, w["mlp/wi_up"], low)
            return x + _mm(m, w["mlp/wo"], low)
        m = jax.nn.gelu(_mm(a, w["mlp/wi_up"], low) + w["mlp/bi"], approximate=True)
        return x + _mm(m, w["mlp/wo"], low) + w["mlp/bo"]

    @functools.partial(jax.jit, static_argnums=(0, 4))
    def _logits(self, top, x, rows, low):
        x = self._norm(x[rows], top, "ln_f")
        return _mm(x, top["unembed"], low)

    ROW_BLOCK = 128

    # -- public --------------------------------------------------------------

    def logits(self, seqs: list, starts: list, pad_to: list, *, low: bool = False):
        """Logits at positions ``starts[i] .. len(seqs[i]) - 1`` of each
        teacher-forced sequence, padded to ``pad_to[i]`` tokens (a multiple
        of ``Q_BLOCK``, or shorter than it).  Returns a list of (n_i, vocab)
        float32 numpy arrays."""
        top = self.draw_top()
        xs, poss = [], []
        for s, n in zip(seqs, pad_to):
            ids = np.zeros(n, np.int32)
            ids[: len(s)] = s
            xs.append(top["embed"][jnp.asarray(ids)])
            poss.append(jnp.arange(n, dtype=jnp.int32))
        for layer in range(self.layers):
            w = self.draw_layer(layer)
            xs = [self._layer(w, x, p, low, self.windows[layer]) for x, p in zip(xs, poss)]
            del w
        out = []
        for x, s, st in zip(xs, seqs, starts):
            n = len(s) - st
            # a whole number of row blocks, so few shapes compile
            r = -(-n // self.ROW_BLOCK) * self.ROW_BLOCK
            rows = np.minimum(st + np.arange(r), x.shape[0] - 1).astype(np.int32)
            out.append(np.asarray(self._logits(top, x, jnp.asarray(rows), low))[:n])
        return out
