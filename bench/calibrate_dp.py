"""Readings that the correctness limits of a data-parallel cell are set from.

    python3 bench/calibrate_dp.py --workload <cell> --seed <first> \
        --seeds 6 --control-seeds 3 [--out <file.json>]

The sharded step runs one algorithm for one (global batch, n_shards) on
any number of chips (launch/train.make_sharded_train_step), so this runs
the cell's step on one chip, at dp 1 with the cell's n_shards.  On a TPU
the two layouts agree to rounding and not bit for bit (PERF.md §4): check
the readings of a seed against the cell's own run of it.  Rows, each
against the reference at the configuration's precision:
  * program: the step, on `--seeds` seeds from `--seed` on;
  * on the first `--control-seeds` of them:
    - int7: the reference at int7 (as calibrate.py);
    - wire8: the step with its gradient wire at 8 bits, where the cell
      states 16;
    - half_batch, no_exchange: faults planted in the step's batch.  Half
      of every batch left out: its second half's rows replaced by the
      first half's.  The exchange between chips left out: every shard's
      rows replaced by the first shard's, so that the step updates with
      that shard's gradient alone, as the first chip would if it never
      heard from the others.
The two steps and the two references compile first, in two threads (the
references into the compile cache: JAX_COMPILATION_CACHE_DIR where it is
set, else run.py's).  The benchmark's own runs never run this script.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

BENCH = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(BENCH) not in sys.path:
    sys.path.insert(0, os.path.dirname(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import correct as C  # noqa: E402
from bench import run as R  # noqa: E402
from bench import spec  # noqa: E402
from bench import traffic as T  # noqa: E402

# the steps, by what make_sharded_train_step is given besides the cell's
STEPS = {"program": {}, "wire8": {"wire_bits": 8}}
PLANTED = ("half_batch", "no_exchange")
_BUILD = threading.local()


def _build_all(progs, params, batch):
    """Compile each step, with its own keywords to the step's builder."""
    from repro.launch import train

    make = train.make_sharded_train_step

    def make_sharded_train_step(*a, **kw):
        return make(*a, **dict(kw, **_BUILD.kw))

    train.make_sharded_train_step = make_sharded_train_step
    try:
        out = {}
        for k, kw in STEPS.items():
            _BUILD.kw = kw
            out[k] = progs[k].build(params, batch)
        return out
    finally:
        train.make_sharded_train_step = make


def _compile_references(configs, cell, traffic, seed):
    """Compile the reference step as reference_readings calls it."""
    batch = T.make_ring(configs[0], traffic, seed)[0]
    key = jax.random.fold_in(jax.random.fold_in(T.seed_key(seed), 2), 0)
    for config in configs:
        p0 = C.init_params(config, seed)
        step = C._reference_step(json.dumps(config), cell["n_shards"])
        step.lower(p0, jax.tree.map(jnp.zeros_like, p0), batch, key).compile()


def planted(fault, batch, n_shards):
    """The batch with `fault` planted in it (see the module's doc)."""
    rows = batch["labels"].shape[0]
    keep = rows // 2 if fault == "half_batch" else rows // n_shards
    return jax.tree.map(
        lambda x: jnp.concatenate([x[:keep]] * (rows // keep)), batch)


def main(argv=None, *, require_tpu: bool = True, root: str = BENCH):
    p = argparse.ArgumentParser("bench/calibrate_dp.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seeds", type=int, default=6)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--out")
    args = p.parse_args(argv)
    cell, config, traffic = spec.resolve(args.workload, root)
    if cell["dp"] == 1:
        raise SystemExit("bench: calibrate_dp.py is for a cell with dp > 1")
    one = dict(cell, dp=1, chips=1)
    R.devices_for(one, require_tpu)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        R.use_compile_cache()
    from bench.program import Program

    int7 = C.lowered_widths(config, 7)
    progs = {k: Program(config, one) for k in STEPS}
    t = time.perf_counter()
    params = C.init_params(config, args.seed)
    batch = R.traffic_ring(config, traffic, args.seed, progs["program"])[0]
    with ThreadPoolExecutor(2) as ex:
        refs = ex.submit(_compile_references, (config, int7), one, traffic,
                         args.seed)
        steps = ex.submit(_build_all, progs, params, batch).result()
        refs.result()
    del params, batch
    R.say(f"compiled {len(steps)} steps and 2 references in "
          f"{time.perf_counter() - t:.1f} s")

    def readings(k, ring, seed):
        prog = progs[k]
        p0 = prog.place(C.init_params(config, seed))
        state = prog.state(C.init_params(config, seed))
        return R.first_steps(R.Loop(steps[k], *state, ring), p0)

    rows = {k: [] for k in ("program", "int7", "wire8", *PLANTED)}
    for i in range(args.seeds):
        seed = args.seed + i
        t = time.perf_counter()
        ring = R.traffic_ring(config, traffic, seed, progs["program"])
        ref = C.reference_readings(config, one, traffic, seed)
        got = readings("program", ring, seed)
        rows["program"].append(dict(C.gaps(got, ref), seed=seed,
                                    loss=got["loss"].tolist(),
                                    ref_loss=ref["loss"].tolist()))
        R.say(f"seed {seed} program losses {got['loss'].tolist()}")
        if i < args.control_seeds:
            rows["int7"].append(dict(C.gaps(C.reference_readings(
                int7, one, traffic, seed), ref), seed=seed))
            rows["wire8"].append(dict(C.gaps(readings("wire8", ring, seed),
                                             ref), seed=seed))
            where = progs["program"].batch_sharding()
            for fault in PLANTED:
                bad = [jax.device_put(planted(fault, b, one["n_shards"]),
                                      where) for b in ring[:C.STEPS]]
                rows[fault].append(dict(C.gaps(
                    readings("program", bad, seed), ref), seed=seed))
        R.say(f"seed {seed} ({time.perf_counter() - t:.1f} s): " + ", ".join(
            f"{k} {rows[k][-1]['loss_gap']:.6g}/{rows[k][-1]['grad_gap']:.6g}"
            f"/{rows[k][-1]['change_gap']:.6g}"
            for k in rows if rows[k] and rows[k][-1]["seed"] == seed))
        del ring

    # as calibrate.py: the lower reading is the largest of the sound runs,
    # an upper one the smallest that a control or fault gives
    def finite(v):
        return [x for x in v if math.isfinite(x)] or [math.nan]
    summary = {k: {n: (max if k == "program" else min)(
        finite([r[n] for r in v])) for n in C.NUMBERS}
        for k, v in rows.items() if v}
    out = {"workload": args.workload, "run_as": {"dp": 1, "chips": 1,
                                                  "n_shards": one["n_shards"]},
           "seeds": [args.seed, args.seeds],
           "lower": summary.pop("program"), "upper": summary, "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in out if k != "rows"}), flush=True)
    return out


if __name__ == "__main__":
    main()
