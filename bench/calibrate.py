"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <cell> --seed <first> --seeds 12 \
        --control-seeds 3 [--controls int7,float32_bf16] \
        [--witness-seeds 3] [--out <file.json>]

In one process, with the cell's step compiled once:
  * program: for each of `--seeds` seeds from `--seed` on, the first three
    steps of the compiled step against the reference at the configuration's
    precision (the lower reading of each number is the largest of these);
  * each control of `--controls` (see CONTROLS), on the first
    `--control-seeds` seeds: the reference at a lower precision, put in the
    program's place, against the reference;
  * half_batch: the reference with half of every batch left out (a planted
    fault), against the reference, on the same seeds;
  * with `--witness-seeds`, on that many seeds: the program with its float32
    layers at one bfloat16 pass (JAX's default on a TPU), against the
    reference and against the reference with its float32 layers at that
    precision (`default_vs_reference`, `default_vs_bf16_reference`).
A step that returns its state unchanged reads 1 on `grad_gap` and
`change_gap` by their definition and needs no run.  The benchmark's own
runs never run this script.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(BENCH) not in sys.path:
    sys.path.insert(0, os.path.dirname(BENCH))

from bench import correct as C  # noqa: E402
from bench import run as R  # noqa: E402
from bench import spec  # noqa: E402


def far_leaves(prog, ref, share=0.3):
    """The look behind a worst-leaf reading: each leaf whose first-step
    change departs from the reference's by more than `share`, with the
    ratio of the two changes and where log2 of the reference's largest
    |gradient| lies between two integers (CQ rounds it to the nearer one;
    near .5 the program and the reference can round apart)."""
    out = []
    for i, (p, r, a) in enumerate(zip(prog["d1"], ref["d1"], ref["a1"])):
        if r > 0 and abs(p - r) > share * r:
            frac = math.log2(a) - math.floor(math.log2(a)) if a > 0 else None
            out.append({"leaf": i, "ratio": p / r, "log2_amax_frac": frac})
    return out


CONTROLS = {
    "int4": lambda c: C.lowered_widths(c, 4),
    "int7": lambda c: C.lowered_widths(c, 7),
    "float32_bf16": C.lowered_float32,
}


def main(argv=None, *, require_tpu: bool = True, root: str = BENCH):
    p = argparse.ArgumentParser("bench/calibrate.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--controls", default="int7,float32_bf16")
    p.add_argument("--witness-seeds", type=int, default=0)
    p.add_argument("--out")
    args = p.parse_args(argv)
    controls = [c for c in args.controls.split(",") if c]
    cell, config, traffic = spec.resolve(args.workload, root)
    R.devices_for(cell, require_tpu)
    R.use_compile_cache()
    from bench.program import Program

    def program_readings(prog, seed, step):
        params = C.init_params(config, seed)
        ring = R.traffic_ring(config, traffic, seed, prog)
        if step is None:
            step = prog.build(params, ring[0])
        p0 = prog.place(C.init_params(config, seed))
        params, opt = prog.state(params)
        return R.first_steps(R.Loop(step, params, opt, ring), p0), step

    prog, step = Program(config, cell), None
    bf16 = C.lowered_float32(config)
    prog_bf16, step_bf16 = None, None
    rows = {k: [] for k in ["program", *controls, "half_batch",
                            "default_vs_reference",
                            "default_vs_bf16_reference"]}
    for i in range(args.seeds):
        seed = args.seed + i
        t = time.perf_counter()
        readings, step = program_readings(prog, seed, step)
        ref = C.reference_readings(config, cell, traffic, seed)
        rows["program"].append(dict(
            C.gaps(readings, ref), seed=seed, loss=readings["loss"].tolist(),
            ref_loss=ref["loss"].tolist(),
            far_leaves=far_leaves(readings, ref)))
        low = {}
        if i < args.control_seeds:
            for name in controls:
                low[name] = C.reference_readings(CONTROLS[name](config),
                                                 cell, traffic, seed)
                rows[name].append(dict(C.gaps(low[name], ref), seed=seed))
            half = C.reference_readings(config, cell, traffic, seed,
                                        half=True)
            rows["half_batch"].append(dict(C.gaps(half, ref), seed=seed))
        if i < args.witness_seeds:
            if prog_bf16 is None:
                prog_bf16 = Program(bf16, cell)
            at_default, step_bf16 = program_readings(prog_bf16, seed,
                                                     step_bf16)
            ref_bf16 = low.get("float32_bf16") or C.reference_readings(
                bf16, cell, traffic, seed)
            rows["default_vs_reference"].append(dict(
                C.gaps(at_default, ref), seed=seed))
            rows["default_vs_bf16_reference"].append(dict(
                C.gaps(at_default, ref_bf16), seed=seed))
        R.say(f"seed {seed} ({time.perf_counter() - t:.1f} s): " + ", ".join(
            f"{k} {rows[k][-1]['loss_gap']:.3g}/{rows[k][-1]['grad_gap']:.3g}"
            f"/{rows[k][-1]['change_gap']:.3g}"
            for k in rows if rows[k] and rows[k][-1]["seed"] == seed))
    # the lower reading is the largest of sound runs (the witness rows are
    # summed up alike); an upper reading is the smallest that a control or
    # fault gives (one that gives no number, as a control whose loss is not
    # finite, sets none)
    def finite(v):
        return [x for x in v if math.isfinite(x)]
    largest = ("program", "default_vs_reference", "default_vs_bf16_reference")
    summary = {k: {n: (max if k in largest else min)(
        finite([r[n] for r in v]) or [math.nan]) for n in C.NUMBERS}
        for k, v in rows.items() if v}
    out = {"workload": args.workload, "seeds": [args.seed, args.seeds],
           "lower": summary.pop("program"),
           "witness": {k: summary.pop(k) for k in largest[1:]
                       if k in summary},
           "upper": summary, "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in out if k != "rows"}))
    return out


if __name__ == "__main__":
    main()
