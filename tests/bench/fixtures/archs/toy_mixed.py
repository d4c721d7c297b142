"""A mixed-layer architecture brought as a file: the dense GQA block, with
a sliding window on the layers that the model block's ``layer_types``
marks ``sliding_attention`` and full causal attention on the
``full_attention`` ones.  The program serves it as a ``block_pattern`` of
``window`` and ``global`` blocks, so its ``layers`` are a list of
per-layer trees."""
from bench import flops
from bench.archs import dense
from bench.reference import Reference as DenseReference

RULES: dict = {}


def windows(model: dict) -> list:
    """Each layer's window, None for full attention."""
    return [model["sliding_window"] if t == "sliding_attention" else None
            for t in model["layer_types"]]


class Reference(DenseReference):
    RULES = RULES

    def __init__(self, model: dict, seed: int):
        super().__init__(model, seed)
        self.windows = windows(model)


class Work:
    """bench/flops.py's counts with each layer's own window: full attention
    on every layer, less the positions a window leaves unattended."""

    def __init__(self, model: dict):
        self.full = flops.dims(dict(model, sliding_window=None))
        self.windows = windows(model)
        self.line = self.full.kv_line_bytes // self.full.layers
        self.per_position = 4 * self.full.heads * self.full.head_dim

    def _unattended(self, pos: int) -> int:
        """Layer-positions that a token at ``pos`` does not attend."""
        return sum(max(0, pos + 1 - w) for w in self.windows if w)

    def prefill(self, s: int) -> tuple[int, int]:
        f, b = self.full.prefill(s)
        return f - self.per_position * sum(self._unattended(p) for p in range(s)), b

    def decode_step(self, positions) -> tuple[int, int]:
        positions = list(positions)
        f, b = self.full.decode_step(positions)
        cut = sum(self._unattended(p) for p in positions)
        return f - self.per_position * cut, b - self.line * cut


def work(model: dict) -> Work:
    return Work(model)


def check_program(model: dict, cfg) -> None:
    dense.check_widths(model, cfg)
    want = tuple(dense.BLOCKS[t] for t in model["layer_types"])
    if want != cfg.blocks:
        raise ValueError(f"model layer_types {want} but the program runs blocks {cfg.blocks}")
