"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (timed as `setup_s`): refuse a cell whose family lacks its
reference or work file (`check_family`), turn on the compile cache at a
fixed path, make the weights and a ring of distinct batches on the device
from the seed, compile the cell's one step shape, and drive the step
through its first three steps, whose readings the correctness check keeps.

--trace 0: a closed loop of back-to-back steps for `--seconds`, the host
enqueuing steps ahead of the device (`AHEAD_*`); the window ends when the
last step's outputs are ready.  Prints `samples_per_s`, `mfu` and `setup_s`;
a sample is one image, or one sequence of the traffic's `seq` tokens.
--trace 1: a shorter traced window; prints the per-layer metrics that the
readers under `metrics/` find in the device trace, with `busy_s`,
`window_s` and a breakdown.

Then the program's state is freed and the plain reference follows the
first three steps; the comparison decides `correct`.  The last line of
stdout is the result as one JSON object.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import correct as C  # noqa: E402
from bench import program, spec  # noqa: E402
from bench import trace as TR  # noqa: E402
from bench import traffic as T  # noqa: E402
from bench.peaks import peaks  # noqa: E402
from bench.work import for_config  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
# The host enqueues steps ahead of the device: at least AHEAD_STEPS, and
# enough for AHEAD_SECONDS of device work, so that a pause of the host
# (a garbage collection, another process on its cores) does not leave the
# device idle.  A ResNet-18 step of 11 ms with 3 steps ahead lost about
# 90 ms in half of the 10 s windows (two levels of samples/s, 0.9% apart);
# a ResNet-50 step of 0.5 s did not.
AHEAD_STEPS = 3
AHEAD_SECONDS = 0.3
TRACE_SECONDS = 3.0    # longest traced window
TRACE_MIN_STEPS = 3


def parse(argv=None):
    p = argparse.ArgumentParser("bench/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, whatever
    JAX_COMPILATION_CACHE_DIR says, so that no other checkout shares it;
    every program is cached, and no size limit from the environment evicts
    one cell's programs (the step and the reference, 20-70 MB each) to
    make room for another's."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return CACHE_DIR


class CompileCounter:
    """Counts compilations and cache lookups while armed."""

    def __init__(self):
        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, *args, **kw):
        if self.armed and ("/jax/core/compile" in event
                           or "/jax/compilation_cache" in event):
            self.n += 1


# What each family has to bring as files of its own: module -> functions.
FAMILY_FILES = {"reference": ("init_params", "train_step"),
                "work": ("model_flops_per_sample",)}


def check_family(config):
    """Refuse, before anything is built, a configuration whose family lacks
    `reference/<family>.py` or `work/<family>.py`, or a function of theirs
    that every run calls."""
    family = config["family"]
    for package, names in FAMILY_FILES.items():
        module = f"bench.{package}.{family}"
        path = f"bench/{package}/{family}.py"
        try:
            mod = importlib.import_module(module)
        except ModuleNotFoundError as e:
            if e.name != module:
                raise
            raise SystemExit(f"bench: family {family!r} has no {path}")
        missing = [n for n in names if not hasattr(mod, n)]
        if missing:
            raise SystemExit(f"bench: {path} lacks {', '.join(missing)}")


def devices_for(cell, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX found {devs[0].platform})")
    if len(devs) < cell["chips"]:
        raise SystemExit(f"bench: the cell asks for {cell['chips']} chips, "
                         f"JAX found {len(devs)}")
    return devs[:cell["chips"]]


def peak_bytes(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Loop:
    """The compiled step, its state and the ring, driven step by step."""

    def __init__(self, step, params, opt, ring):
        self.step, self.params, self.opt, self.ring = step, params, opt, ring
        self.i = 0
        self.losses = []
        self.ahead = AHEAD_STEPS

    def one(self):
        with jax.profiler.TraceAnnotation("bench.batch"):
            batch = self.ring[self.i % len(self.ring)]
            idx = np.int32(self.i)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            self.params, self.opt, m = self.step(self.params, self.opt,
                                                 batch, idx)
        self.losses.append(m["loss"])
        self.i += 1
        return m["loss"]

    def run(self, seconds: float | None = None, steps: int | None = None):
        """Back-to-back steps until `seconds` have passed (or `steps` are
        done); returns (steps, seconds until the last one is ready)."""
        t0, n = time.perf_counter(), 0
        while True:
            if len(self.losses) >= self.ahead:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    self.losses[-self.ahead].block_until_ready()
            self.one()
            n += 1
            if steps is not None and n >= steps:
                break
            if seconds is not None and time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready((self.params, self.opt, self.losses[-1]))
        return n, time.perf_counter() - t0

    def failed(self, last: int) -> int:
        vals = np.asarray(jax.device_get(self.losses[-last:]))
        return int((~np.isfinite(vals)).sum())


def first_steps(loop, p0):
    """The readings of the first three steps (correct.py)."""
    losses = []
    for i in range(C.STEPS):
        losses.append(loop.one())
        if i == 0:
            d1 = C.leaf_norms(loop.params, p0)
    d3 = C.leaf_norms(loop.params, p0)
    out = jax.device_get({"loss": losses, "d1": d1, "d3": d3})
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def traced_window(loop, devs, step_s: float, cats: dict):
    """A short traced window; (reduced trace, steps)."""
    steps = max(TRACE_MIN_STEPS, int(TRACE_SECONDS / max(step_s, 1e-3)))
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            loop.run(steps=steps)
        jax.profiler.stop_trace()
        return TR.reduce(TR.find_xplane(tmp), len(devs), cats), steps
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def read_metrics(ctx, root: str = BENCH):
    """Every reader under metrics/ that finds something to read; a reader
    is `metrics/<metric>.py` with a `UNIT` and a `read(ctx)`."""
    out = {}
    mdir = os.path.join(root, "metrics")
    for fname in sorted(os.listdir(mdir)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        name = fname[:-3]
        loader = importlib.util.spec_from_file_location(
            f"bench_metric_{name}", os.path.join(mdir, fname))
        mod = importlib.util.module_from_spec(loader)
        loader.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": value, "unit": mod.UNIT}
    return out


def run(args, *, require_tpu: bool = True, fault=None, root: str = BENCH):
    """One run of a cell; returns the result dict.  `fault` wraps the
    compiled step (tests plant faults with it)."""
    cell, config, traffic = spec.resolve(args.workload, root)
    check_family(config)
    if traffic["ring"] < C.STEPS:
        raise SystemExit(f"bench: traffic {cell['traffic']!r} keeps "
                         f"{traffic['ring']} batches; the correctness check "
                         f"steps through {C.STEPS} distinct batches")
    program.check_present()
    devs = devices_for(cell, require_tpu)

    say(f"compile cache {use_compile_cache()}")
    counter = CompileCounter()
    kind = devs[0].device_kind
    peak = peaks(kind) if require_tpu else None
    say(f"device {kind} x{len(devs)}; cell {args.workload}: "
        f"{config['name']} batch {traffic['batch']} dp {cell['dp']} "
        f"n_shards {cell['n_shards']}")

    prog = program.Program(config, cell)
    params = C.init_params(config, args.seed)
    prog.check_tree(params)
    p0 = C.init_params(config, args.seed)
    ring = traffic_ring(config, traffic, args.seed, prog)
    compiled = prog.build(params, ring[0])
    params, opt = prog.state(params)
    p0 = prog.place(p0)
    step = compiled if fault is None else fault(compiled)
    loop = Loop(step, params, opt, ring)
    readings = first_steps(loop, p0)
    del p0
    t = time.perf_counter()
    loop.run(steps=2)
    step_s = (time.perf_counter() - t) / 2
    loop.ahead = max(AHEAD_STEPS, math.ceil(AHEAD_SECONDS / step_s))
    gc.collect()
    gc.freeze()        # set-up's objects: never scanned again in the window
    setup_s = time.perf_counter() - T_START
    oracle = prog.oracle_calls()
    say(f"setup_s {setup_s:.3f}; warm step {step_s:.4f} s, "
        f"{loop.ahead} ahead; "
        f"oracle calls {oracle}; losses {readings['loss'].tolist()}")

    counter.armed = True
    metrics, dev_extra, breakdown = {}, {}, None
    if args.trace:
        cats = TR.categories(compiled.as_text())
        red, steps = traced_window(loop, devs, step_s, cats)
        ctx = dict(red, steps=steps, config=config, traffic=traffic,
                   cell=cell, peaks=peak, oracle_calls=oracle,
                   chips=len(devs))
        metrics = read_metrics(ctx, root)
        dev_extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        say(f"traced {steps} steps: busy {red['busy_s']:.6f} s of "
            f"{red['window_s']:.6f} s")
        breakdown = red["breakdown"]
    else:
        steps, secs = loop.run(seconds=args.seconds)
        sps = steps * traffic["batch"] / secs
        flops = for_config(config).model_flops_per_sample(config, traffic)
        metrics["samples_per_s"] = {"value": sps, "unit": "samples/s"}
        if peak is not None:
            metrics["mfu"] = {"value": 100.0 * sps * flops
                              / (len(devs) * peak["int8_ops"]), "unit": "%"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        say(f"window {steps} steps in {secs:.4f} s")
    counter.armed = False
    failed = loop.failed(steps)
    mem = peak_bytes(devs)
    say(f"compilations in the window {counter.n}; non-finite losses "
        f"{failed}; peak device bytes {mem}")

    del loop, step, compiled, params, opt, ring
    t = time.perf_counter()
    g = C.gaps(readings, C.reference_readings(config, cell, traffic,
                                              args.seed))
    say(f"reference {time.perf_counter() - t:.3f} s; leaves counted "
        f"{g['leaves_counted']}; not compared: grad_gap_worst "
        f"{g['grad_gap_worst']!r} change_gap_worst {g['change_gap_worst']!r}")
    correct, checks = C.judge(g, cell["limits"])
    checks["compiles_in_window"] = {"value": counter.n, "limit": 0}
    checks["nonfinite_losses"] = {"value": failed, "limit": 0}
    correct = correct and counter.n == 0 and failed == 0

    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics,
              "device": dict({"platform": devs[0].platform, "kind": kind,
                              "count": len(devs), "memory_peak_bytes": mem},
                             **dev_extra)}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def traffic_ring(config, traffic, seed, prog):
    return T.make_ring(config, traffic, seed,
                       out_shardings=prog.batch_sharding())


def main(argv=None) -> int:
    args = parse(argv)
    result = run(args)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
