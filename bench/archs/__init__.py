"""Architecture modules: everything in the benchmark that depends on the
shape of a model's block.

A configuration file may name one, ``"architecture":
"bench/archs/<name>.py"`` (a path under the checkout's root);
bench/spec.py loads it and hands it to the harness as ``cell["arch"]``.
Without the key a cell takes :mod:`bench.archs.dense`, the dense GQA/MHA
block.  A module provides:

* ``Reference(model, seed)``: the plain reference (nothing of the program
  imported), with ``Q_BLOCK`` and ``logits(seqs, starts, pad_to, *,
  low=False)`` as bench/reference.py has them, and ``draw_layer(layer)``
  and ``draw_top()``, each ``{program leaf path: float32 array}`` keyed by
  the program's own paths (``attn/wq``, ``mlp/wi_gate``, ``moe/router``
  within a layer; ``embed``, ``ln_f`` outside), drawn again from the seed
  with the module's ``RULES`` (bench/reference.py's ``Reference`` draws
  with its class attribute ``RULES``: a subclass sets it to the module's);
* ``RULES``: leaf path, or its end, -> (mean, std rule), consulted before
  bench/weights.py's own (``"axis:<i>"``: 1/sqrt(shape[i]));
* ``work(model)``: an object with ``prefill(s)`` and
  ``decode_step(positions)``, each ``(FLOPs, bytes)`` under the counting
  rules of bench/flops.py;
* ``check_program(model, cfg)``: raises ValueError on any width, block type
  or size where the file's ``model`` block and the program's config
  disagree.

Weights come from bench/weights.py (``layer_leaves``, ``leaf``,
``key_of``), keyed by the seed, the leaf's path without the layer index
and the layer, so the reference's redraw equals the program's draw bit for
bit whether the program stacks its layers or lists them
(``bench.control`` compares them).  A module may build on
bench/reference.py, bench/flops.py and this package's ``dense``, as the
mixed-layer toy of tests/bench/fixtures/archs/toy_mixed.py does.
"""
