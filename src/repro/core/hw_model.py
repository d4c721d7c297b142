"""Analytical unit-gate hardware cost model for the Table 3 left half.

Vivado synthesis is unavailable here (docs/numerics.md): each design is described
as a netlist of adders / muxes / ROM bits, costed by a classic unit-gate
model, then calibrated to the paper's Artix-7 scale with a *single* global
factor per metric, fit on the **E2AFS row** — the one datapath we reproduce
bit-exactly from the paper, so its netlist is known, not reconstructed.

Honest-reporting notes (EXPERIMENTS.md carries the full discussion):
  * Baseline netlists are *our reconstructions* (docs/numerics.md).  Our ESAS is
    level-1-only and therefore *simpler* than the real ESAS — consistent with
    the paper reporting ESAS at 54 LUTs vs E2AFS's 37.  Proxy costs for
    baselines therefore under-estimate the real baselines, which only
    *strengthens* the paper's claim (E2AFS beats even simplified baselines on
    accuracy at comparable proxy cost).
  * These are proxies, never measured watts.

Unit-gate conventions (Parhami, "Computer Arithmetic"):
  * adder: area 5 gate-eq/bit; FPGA carry chain depth ~ 2 + width/4
  * 2:1 mux: area 3 gate-eq/bit, depth 1
  * ROM: area 0.25 gate-eq/bit, depth 1 (LUT-mapped table)
  * fixed shifts / bit concatenation: wiring, free
Switching proxy: adders 0.5/bit, muxes 0.25/bit, ROM 0.125/bit, +6 I/O floor.

Datapath structure used for the critical paths (exponent and mantissa paths
run in parallel; mantissa dominates):
  E2AFS : add12(man+341) -> mux(y_hi) -> add11(x1.5 via t+t>>1) -> mux(parity)
          [even-path constant subtract runs in parallel with the odd path]
  ESAS  : add11(x1.5) -> mux(parity)            (1 + man>>s is free concat)
  CWAHA : ROM lookup -> mux(parity)
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

__all__ = [
    "Netlist",
    "NETLISTS",
    "cost",
    "calibrated_table",
    "PAPER_TABLE3",
    "ChipModel",
    "TPU_V5E",
    "INTERPRET_CPU",
    "CHIPS",
    "chip_for_backend",
    "chip_for_kind",
]


@dataclasses.dataclass(frozen=True)
class Netlist:
    """(width, count) component inventories + explicit critical path."""

    adders: Tuple[Tuple[int, int], ...] = ()
    muxes: Tuple[Tuple[int, int], ...] = ()
    rom_bits: int = 0
    critical_path: Tuple[Tuple[str, int], ...] = ()


NETLISTS: Dict[str, Netlist] = {
    # E2AFS (bit-exact from the paper): exponent sub+add (5b, parallel);
    # mantissa: man+341 (12b), even-path constant subtract (11b), x1.5 adder
    # (11b); muxes: y_hi select (11b), parity select (11b).
    "e2afs": Netlist(
        adders=((5, 2), (12, 1), (11, 2)),
        muxes=((11, 2),),
        critical_path=(("add", 12), ("mux", 11), ("add", 11), ("mux", 11)),
    ),
    # ESAS reconstruction (level-1 only): exponent pair + x1.5 adder + parity mux.
    "esas": Netlist(
        adders=((5, 2), (11, 2)),
        muxes=((11, 1),),
        critical_path=(("add", 11), ("add", 11), ("mux", 11)),
    ),
    # CWAHA-k reconstruction: exponent pair + 2 ROM tables + parity mux.
    "cwaha4": Netlist(
        adders=((5, 2),),
        muxes=((10, 1),),
        rom_bits=2 * 4 * 10,
        critical_path=(("rom", 10), ("mux", 10)),
    ),
    "cwaha8": Netlist(
        adders=((5, 2),),
        muxes=((10, 1),),
        rom_bits=2 * 8 * 10,
        critical_path=(("rom", 10), ("mux", 10)),
    ),
}

_AREA = {"add": 5.0, "mux": 3.0, "rom": 0.25}
_TOGGLE = {"add": 0.5, "mux": 0.25, "rom": 0.125}

# Paper's Table 3 (left half), for calibration and side-by-side printing.
PAPER_TABLE3 = {
    "esas": {"luts": 54, "dp_mw": 7.98, "cpd_ns": 5.242, "pdp_pj": 41.8312},
    "cwaha4": {"luts": 25, "dp_mw": 8.88, "cpd_ns": 5.027, "pdp_pj": 44.6398},
    "cwaha8": {"luts": 45, "dp_mw": 9.99, "cpd_ns": 5.732, "pdp_pj": 57.2627},
    "e2afs": {"luts": 37, "dp_mw": 7.63, "cpd_ns": 4.639, "pdp_pj": 35.3955},
}


def cost(name: str) -> Dict[str, float]:
    """Raw unit-gate metrics: area (gate-eq), depth (gate-delays), switching."""
    n = NETLISTS[name]
    area = sum(w * c * _AREA["add"] for w, c in n.adders)
    area += sum(w * c * _AREA["mux"] for w, c in n.muxes)
    area += n.rom_bits * _AREA["rom"]
    depth = 0.0
    for kind, width in n.critical_path:
        depth += (2.0 + width / 4.0) if kind == "add" else 1.0
    switching = sum(w * c * _TOGGLE["add"] for w, c in n.adders)
    switching += sum(w * c * _TOGGLE["mux"] for w, c in n.muxes)
    switching += n.rom_bits * _TOGGLE["rom"]
    switching += 6.0  # I/O register floor
    return {"area": area, "depth": depth, "switching": switching}


# ---------------------------------------------------------------------------
# Chip-level roofline constants
# ---------------------------------------------------------------------------
#
# The unit-gate model above prices one datapath; kernel tiling needs the
# complement — what one *chip* sustains per second and what one grid step
# costs to launch.  Both the roofline tables (benchmarks/roofline.py,
# launch/dryrun.py) and the autotune tile priors (kernels/tuning.py) read
# their constants from here so a recalibration lands everywhere at once.


@dataclasses.dataclass(frozen=True)
class ChipModel:
    """Per-chip roofline terms for the tile-time prior.

    ``peak_flops`` is the sustained per-element op rate of the tile pipeline
    (bf16 MXU peak for real TPU; an effective emulation rate for the Pallas
    interpreter, whose per-element bookkeeping — not HBM — is the bottleneck).
    ``step_overhead_s`` is the fixed cost of one grid step: ~us-scale kernel
    dispatch when compiled, ~ms-scale traced fori iteration when interpreted.
    """

    name: str
    peak_flops: float  # elementwise op/s the tile pipeline retires
    hbm_bw: float  # bytes/s
    vmem_bytes: int  # per-core fast-memory budget a tile must fit in
    step_overhead_s: float  # fixed cost per grid step


TPU_V5E = ChipModel(
    name="tpu-v5e",
    peak_flops=197e12,  # bf16 peak; shared with the roofline tables
    hbm_bw=819e9,
    vmem_bytes=16 * 2**20,
    step_overhead_s=2e-6,
)

INTERPRET_CPU = ChipModel(
    name="pallas-interpret-cpu",
    peak_flops=2e9,
    hbm_bw=2e10,
    vmem_bytes=256 * 2**20,  # emulated VMEM: host memory, effectively uncapped
    step_overhead_s=1e-3,
)


# compiled-backend chip constants, keyed by jax's ``Device.device_kind``
CHIPS: Dict[str, ChipModel] = {
    "TPU v5 lite": TPU_V5E,  # v5e (Google Cloud "TPU v5e" documentation)
}


def chip_for_kind(device_kind: str) -> ChipModel:
    """The chip constants for a device kind; a device that is not in
    :data:`CHIPS` is an error, never a default."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no chip constants for device kind {device_kind!r}; "
            f"known: {sorted(CHIPS)}"
        ) from None


def chip_for_backend(interpret: bool) -> ChipModel:
    """The chip whose roofline terms model the resolved kernel backend: the
    interpreter's stand-in, or the constants of the device JAX runs on."""
    if interpret:
        return INTERPRET_CPU
    import jax

    return chip_for_kind(jax.devices()[0].device_kind)


def calibrated_table() -> Dict[str, Dict[str, float]]:
    """Scale raw metrics to the paper's units using the E2AFS row only."""
    ref_raw = cost("e2afs")
    ref_paper = PAPER_TABLE3["e2afs"]
    k_lut = ref_paper["luts"] / ref_raw["area"]
    k_cpd = ref_paper["cpd_ns"] / ref_raw["depth"]
    k_dp = ref_paper["dp_mw"] / ref_raw["switching"]
    out = {}
    for name in NETLISTS:
        raw = cost(name)
        luts = raw["area"] * k_lut
        cpd = raw["depth"] * k_cpd
        dp = raw["switching"] * k_dp
        out[name] = {
            "luts_proxy": luts,
            "cpd_ns_proxy": cpd,
            "dp_mw_proxy": dp,
            "pdp_pj_proxy": cpd * dp,
        }
    return out
