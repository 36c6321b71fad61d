"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch × shape × mesh), all in seconds:
    compute    = HLO_FLOPs_per_device / peak_FLOP/s          (MXU)
    memory     = HLO_bytes_per_device / HBM_bw               (HBM)
    collective = collective_bytes_per_device / link_bw       (ICI)

cost_analysis() is per-device for SPMD executables (verified empirically:
a (256,512)x(512,1024) matmul over 8 devices reports 2MNK/8 flops), so the
per-device forms above equal the spec's global/(chips*rate) forms.

Hardware constants come from PEAKS, one row per jax `device_kind`; a kind
missing from the table raises rather than borrowing another chip's peaks.
The dry-run artifacts model a v5e (TARGET_KIND).  Both MXU fractions are
reported; the headline roofline fraction uses the bf16 peak, the int8
column shows what the WAGEUBN datapath unlocks.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re

# Published per-chip peaks, keyed by jax's device_kind.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, HBM at
# 819 GB/s, 1,600 Gbit/s (200 GB/s) of inter-chip interconnect.
PEAKS = {
    "TPU v5 lite": {"bf16": 197e12, "int8": 393e12, "hbm": 819e9,
                    "ici": 200e9},
}
TARGET_KIND = "TPU v5 lite"


def peaks(kind: str) -> dict:
    """The PEAKS row for `kind`; an unknown device kind is an error."""
    if kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind {kind!r} "
                         f"(known: {sorted(PEAKS)})")
    return PEAKS[kind]


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLL_RE = re.compile(
    r"=\s+(?P<lhs>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all"
    r"|collective-permute)(?P<start>-start)?\(")
_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|s16|s32|s64|u8|u16|u32"
                       r"|u64)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective byte accounting from the scheduled HLO.

    Scheduled HLO names (not re-types) operands, so we read the RESULT shape
    and convert to operand bytes per op semantics:
        all-reduce:         operand == result
        all-gather:         operand == result / group_size
        reduce-scatter:     operand == result * group_size
        all-to-all / collective-permute: operand == result
    Also records a ring wire-traffic estimate per op ("wire_bytes"):
        all-reduce 2*(g-1)/g * size; all-gather/reduce-scatter (g-1)/g * full
        size; permute/all-to-all = size.
    Returns {op: {"bytes", "wire_bytes", "count"}}.
    """
    out: dict = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        result_bytes = sum(_shape_bytes(d, s)
                           for d, s in _SHAPE_RE.findall(m.group("lhs")))
        if m.group("start"):
            result_bytes //= 2      # (operand, result) tuple of async op
        g = max(_group_size(line), 1)
        if op == "all-gather":
            operand = result_bytes // g
            wire = result_bytes * (g - 1) // g
        elif op == "reduce-scatter":
            operand = result_bytes * g
            wire = operand * (g - 1) // g
        elif op == "all-reduce":
            operand = result_bytes
            wire = 2 * result_bytes * (g - 1) // g
        else:
            operand = result_bytes
            wire = result_bytes
        rec = out.setdefault(op, {"bytes": 0, "wire_bytes": 0, "count": 0})
        rec["bytes"] += operand
        rec["wire_bytes"] += wire
        rec["count"] += 1
    return out


def terms(art: dict, kind: str = TARGET_KIND) -> dict:
    """Roofline terms (seconds) + fractions for one artifact dict."""
    pk = peaks(kind)
    flops = art["flops_per_device"]
    mem_bytes = art["bytes_per_device"]
    coll_bytes = art["collective_bytes_per_device"]
    t_c = flops / pk["bf16"]
    t_c8 = flops / pk["int8"]
    t_m = mem_bytes / pk["hbm"]
    t_l = coll_bytes / pk["ici"]
    dominant = max(("compute", t_c), ("memory", t_m),
                   ("collective", t_l), key=lambda kv: kv[1])[0]
    total = max(t_c, t_m, t_l)
    chips = art["devices"]
    model_flops = art.get("model_flops_global", 0.0)
    hlo_global = flops * chips
    return {
        "compute_s": t_c, "compute_int8_s": t_c8, "memory_s": t_m,
        "collective_s": t_l, "dominant": dominant,
        "roofline_fraction": (t_c / total) if total else 0.0,
        "useful_ratio": (model_flops / hlo_global) if hlo_global else 0.0,
        "step_lower_bound_s": total,
    }


def load_artifacts(art_dir: str):
    arts = []
    for f in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(f) as fh:
            arts.append(json.load(fh))
    return arts


def render_table(arts, mesh_filter="single") -> str:
    rows = ["| arch | shape | kind | compute_s | memory_s | collective_s |"
            " dominant | roofline_frac | useful_ratio |",
            "|---|---|---|---|---|---|---|---|---|"]
    for a in arts:
        if a["mesh"] != mesh_filter:
            continue
        t = terms(a)
        rows.append(
            f"| {a['arch']} | {a['shape']} | {a['kind']} "
            f"| {t['compute_s']:.3e} | {t['memory_s']:.3e} "
            f"| {t['collective_s']:.3e} | {t['dominant']} "
            f"| {t['roofline_fraction']:.2%} | {t['useful_ratio']:.2f} |")
    return "\n".join(rows)


def main(argv=None):
    p = argparse.ArgumentParser("repro.launch.roofline")
    p.add_argument("--art-dir", default="artifacts/dryrun")
    p.add_argument("--mesh", default="single")
    args = p.parse_args(argv)
    arts = load_artifacts(args.art_dir)
    print(render_table(arts, args.mesh))
    print()
    for a in arts:
        if a["mesh"] != args.mesh:
            continue
        t = terms(a)
        print(f"{a['arch']:24s} {a['shape']:12s} dominant={t['dominant']:10s}"
              f" bound={t['step_lower_bound_s']:.4e}s peak/dev="
              f"{a['mem_analysis'].get('peak_bytes_est', 0)/2**30:.2f}GiB")


if __name__ == "__main__":
    main()
