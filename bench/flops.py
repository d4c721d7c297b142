"""Operations and bytes the algorithm needs, computed from shapes alone.

These are the numerators of every roofline share and of ``serve_mfu``: a
``Dims`` is the dense default's ``work(model)`` (bench/archs/), and an
architecture module's own counts keep these rules.  They count the work a
request needs, whatever implements it:

* matrix FLOPs are 2 per weight per token over the non-embedding weights,
  plus the unembedding once per token whose logits are used (the last
  prompt token of a prefill, every needed decode step);
* attention FLOPs are 4 * heads * head_dim per layer per position that a
  token attends (scores and weighted sum): a token at position ``p``
  attends ``p + 1`` positions, at most the sliding window;
* bytes are every weight read once per forward (a prefill, or one decode
  step whatever its batch), the embedding rows looked up, and the key and
  value lines each token writes and each decode row attends.

Padding rows, whole-cache reads, masked positions and recomputation are not
counted, so a change that stops doing them raises a share and none can pass
100% by counting work that was not needed.  Biases (the MLP's, and the
attention projections' of a model with ``use_bias``) are left out: under
0.01% of the work.

The counts are the whole model's, however many chips share it.  On a mesh
of ``n`` chips a share divides them by ``n`` times one chip's peak
(bench/run.py ``read_per_layer`` hands the readers those peaks) and by the
time of one device, which runs its part of every program in step with the
others: it is the share of all ``n`` chips' roofline, or peak, that the
work needed.  Work that tensor parallelism repeats on every chip (norms,
the replicated activations) and the exchanges between chips count for
nothing.
"""
from __future__ import annotations

import dataclasses

__all__ = ["Dims", "dims", "prefill", "decode_step", "min_time"]

BYTES = 2  # bf16 weights, activations and cache lines


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    window: int | None
    swiglu: bool

    @property
    def layer_params(self) -> int:
        attn = self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
        mlp = (3 if self.swiglu else 2) * self.d * self.ff
        return attn + mlp

    @property
    def body_params(self) -> int:
        return self.layers * self.layer_params

    @property
    def unembed_params(self) -> int:
        return self.d * self.vocab

    @property
    def kv_line_bytes(self) -> int:
        """One token's keys and values over all layers."""
        return self.layers * 2 * self.kv_heads * self.head_dim * BYTES

    def attended(self, pos: int) -> int:
        n = pos + 1
        return n if self.window is None else min(n, self.window)

    def attn_flops(self, attended: int) -> int:
        return 4 * self.heads * self.head_dim * self.layers * attended

    def prefill(self, s: int) -> tuple[int, int]:
        return prefill(self, s)

    def decode_step(self, positions) -> tuple[int, int]:
        return decode_step(self, positions)


def dims(model: dict) -> Dims:
    return Dims(
        d=model["hidden_size"],
        layers=model["num_hidden_layers"],
        heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        ff=model["intermediate_size"],
        vocab=model["vocab_size"],
        window=model.get("sliding_window"),
        swiglu=model["hidden_act"] == "silu",
    )


def prefill(m: Dims, s: int) -> tuple[int, int]:
    """(FLOPs, bytes) to prefill an ``s``-token prompt and choose its first
    token."""
    attn = sum(m.attn_flops(m.attended(p)) for p in range(s))
    flops = 2 * m.body_params * s + 2 * m.unembed_params + attn
    nbytes = ((m.body_params + m.unembed_params) * BYTES
              + s * m.d * BYTES + s * m.kv_line_bytes)
    return flops, nbytes


def decode_step(m: Dims, positions) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode step whose needed rows feed tokens at
    ``positions`` (each attends the positions before it and itself)."""
    positions = list(positions)
    if not positions:
        return 0, 0
    rows = len(positions)
    att = [m.attended(p) for p in positions]
    flops = rows * 2 * (m.body_params + m.unembed_params) + sum(
        m.attn_flops(a) for a in att)
    nbytes = ((m.body_params + m.unembed_params) * BYTES
              + rows * (m.d * BYTES + m.kv_line_bytes)
              + sum(att) * m.kv_line_bytes)
    return flops, nbytes


def min_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops"]
    tb = nbytes / peaks["hbm_bytes_s"]
    return (tc, "compute") if tc >= tb else (tb, "memory")
