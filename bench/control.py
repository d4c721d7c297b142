"""Readings that set a cell's correctness limit, on the chip.

    python3 -m bench.control --workload qwen3-4b.chat-steady \\
        --seeds 101-112 --control-seeds 3 --seconds 15

First, on the first seed, the reference's redraw of the weights is compared
with the program's draw, element by element, on the device, leaf by
program path (``attn/wq`` of each layer, whether the program stacks its
layers or lists them): a leaf that differs would make the check compare
the program with another model.  Then, for each seed, in one process: a
run of the cell's timed path at its own load for a short window (every
request served and drained), and the reference over the run's sample, as
``bench.run`` does.  The widest served gap over these sound runs is the
lower reading.  On the first ``--control-seeds`` seeds the reference is
also run in fp8 (the control: the cell's architecture's ``Reference``
with ``low=True``): the gap of the token it puts first at each of the
same positions, the smallest of which is the upper reading.  Both go
through the harness's own comparison (bench/check.py ``verdict``) with the
limits in ``bench/limits/<cell>.json``, and each line prints the
``correct`` that a run would: true for a sound run, false for the control.
Prints one JSON line per seed and a summary line; exits 4 when a weight
differs, a sound run is not correct, or a control is.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import check, harness, spec
from bench.run import NoChip, check_devices


def _seeds(text: str) -> list[int]:
    if "-" in text:
        a, b = text.split("-")
        return list(range(int(a), int(b) + 1))
    return [int(s) for s in text.split(",")]


def _layer_leaves(layers, i: int) -> dict:
    """{path within a layer: array} of layer ``i`` of the program's tree,
    whether its layers are stacked or listed."""
    import jax

    from bench import weights

    if isinstance(layers, list):
        return {weights.path_and_layer(k)[0]: v
                for k, v in jax.tree_util.tree_leaves_with_path(layers[i])}
    return {weights.path_and_layer(k)[0]: v[i]
            for k, v in jax.tree_util.tree_leaves_with_path(layers)}


def weight_mismatches(cell: dict, seed: int) -> dict:
    """{program leaf path: elements that differ} between the program's
    one-call draw, on the cell's mesh if it has one, and the reference's
    redraw (``draw_layer``, ``draw_top``) on the first device, every layer
    and top-level leaf, by program path over stacked and listed layers.  A
    leaf the reference does not draw differs in every element."""
    import jax
    import jax.numpy as jnp

    arch = cell["arch"]
    cfg = harness.program_config(cell["config"], arch)
    prog = harness.program_weights(cfg, seed, harness.program_mesh(cell["config"]),
                                   arch.RULES)
    ref = arch.Reference(cell["config"]["model"], seed)
    here = jax.devices()[0]

    def differ(mine, theirs):
        if theirs is None or theirs.shape != mine.shape:
            return int(mine.size)
        return int(jnp.sum(jax.device_put(mine, here).astype(jnp.float32) != theirs))

    out: dict = {}
    for layer in range(cfg.n_layers):
        w = ref.draw_layer(layer)
        for path, leaf in _layer_leaves(prog["layers"], layer).items():
            out[path] = out.get(path, 0) + differ(leaf, w.get(path))
        del w
    top = ref.draw_top()
    for k, leaf in prog.items():
        if k != "layers":
            out[k] = differ(leaf[:, : ref.vocab] if k == "unembed" else leaf, top.get(k))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    harness._src_on_path(cell["root"])
    import jax

    try:
        check_devices(jax.devices(), cell["entry"]["chips"])
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache(cell["root"])
    Reference = cell["arch"].Reference
    seeds = _seeds(args.seeds)
    mismatched = weight_mismatches(cell, seeds[0])
    print(json.dumps({"weights_seed": seeds[0], "elements_differing": mismatched}), flush=True)
    rows = []
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        served = harness.serve(cell, seed, args.seconds, trace=False, t_start=t,
                               log=lambda m: None)
        prompts = {r.uid: r.prompt for r in served["requests"]}
        failed = check.not_served(served["requests"], served["completions"])
        row = check.run_check(Reference(cell["config"]["model"], seed),
                              served["completions"], prompts, seed, cell["traffic"],
                              Reference.Q_BLOCK, control=i < args.control_seeds)
        row["correct"], _ = check.verdict(dict(row, not_served=failed), cell["limits"])
        if "control_gap" in row:
            row["control_correct"], _ = check.verdict(
                dict(row, logit_gap=row["control_gap"], not_served=failed), cell["limits"])
        row.update(seed=seed, requests=len(served["requests"]), not_served=failed,
                   seconds=time.perf_counter() - t)
        rows.append(row)
        print(json.dumps(row), flush=True)
    ctl = [r for r in rows if "control_gap" in r]
    summary = {"summary": args.workload, "limits": cell["limits"],
               "lower": max(r["logit_gap"] for r in rows),
               "upper": min(r["control_gap"] for r in ctl) if ctl else None,
               "sound_correct": sum(r["correct"] for r in rows), "sound_runs": len(rows),
               "control_correct": sum(r["control_correct"] for r in ctl),
               "control_runs": len(ctl),
               "gaps": sorted(r["logit_gap"] for r in rows)}
    print(json.dumps(summary), flush=True)
    bad = (any(mismatched.values()) or summary["sound_correct"] < len(rows)
           or summary["control_correct"])
    return 4 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
