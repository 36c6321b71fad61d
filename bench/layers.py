"""Device time by the program's own layer names.

The program names its layers with `jax.named_scope` (PERF.md §3): every
instruction of the compiled HLO keeps the JAX name stack it was traced
under as `metadata={op_name="..."}`, and the trace names each device op by
its instruction (trace.instruction_name).  This module joins the two.

    python3 bench/layers.py --workload <cell> --seed <n> [--seconds <s>] \
        [--out <dir>]

on a machine with the cell's chips: compiles the cell's step as run.py
does, times back-to-back steps with the profiler off for `--seconds` (10,
run.py's window in the benchmark), traces a window as run.py does, and
prints one JSON line: the device time per step split by phase, layer and
ResNet stage, the metrics below, and the step time inside the traced window
against the untraced one (what tracing costs).  With `--out`, the compiled
HLO text and the trace are written there too.

Attribution, from an op's op_name (its fusion's own, for a fusion):
  * phase: "backward" if it holds `transpose(`; else "optimizer" if it
    holds the `momentum_update` scope; else "wire" if it holds the `wire`
    scope; else "forward".  An op with no op_name (a copy XLA put in) is
    "unscoped" and belongs to no phase, so the phases sum to the device
    time.
  * layer: the innermost of LAYER_SCOPES in the op_name, or "unscoped".
    XLA gives a fusion the op_name of one of its ops, so a layer fused into
    another's op (an amax into the conv it follows) counts as that other
    layer.  "mixed" counts, besides, the ops whose fused computation holds
    more than one layer, and `holding` gives per layer the time of every op
    that holds it.
  * stage: the `stage<i>` scope of models/resnet.py, where there is one.
A `while` op's trace event spans the events of its body, so container ops
are left out and the phases sum to the device's op time.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace as TR  # noqa: E402

# the scopes src/repro puts around each layer (PERF.md §3)
LAYER_SCOPES = ("stem", "head", "qconv", "q_e2", "qact", "q_e1", "qweight",
                "ubn", "amax", "momentum_update", "cq", "update", "wire")
PHASES = ("forward", "backward", "optimizer", "wire", "unscoped")
ASYNC_LINE = "Async XLA Ops"

_WORD = re.compile(r"[A-Za-z_]\w*")
_STAGE = re.compile(r"\bstage\d+\b")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# a fusion's own computations; not to_apply, body or condition (as
# trace._CALLS): XLA shares one reducer among reductions of every layer, and
# a `while` is a container whose body's ops have trace events of their own
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
# ops whose trace events span the events of the computations they run
_CONTAINER = re.compile(r"\s(?:while|conditional|call)\(")


def _computations(hlo_text: str) -> dict:
    """{computation: [(instruction, its line), ...]} of an HLO module.  A
    computation's header starts in the first column and opens its body."""
    comps, cur = defaultdict(list), None
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ") and line.rstrip().endswith("{"):
            m = TR._COMP.match(line)
            cur = m.group(1) if m else None
            continue
        m = TR._INSTR.match(line)
        if m and cur is not None:
            comps[cur].append((m.group(1), line))
    return comps


def _op_name(line: str) -> str:
    m = _OP_NAME.search(line)
    return m.group(1) if m else ""


def parse(hlo_text: str) -> dict:
    """What the join needs of a compiled module:
      * names: {instruction: op_name} for every instruction; "" where it
        has none.  A fusion has its own;
      * inside: {instruction: layers of the ops in the computations it
        calls}, for each fusion (recursively);
      * containers: while, conditional and call instructions, whose trace
        events span their bodies' events and are not counted."""
    comps = _computations(hlo_text)
    memo = {}

    def held(comp, seen=()):
        if comp not in memo:
            found = set()
            for _, line in comps.get(comp, ()):
                found.add(layer(_op_name(line)))
                for callee in _CALLS.findall(line):
                    if callee not in seen:
                        found |= held(callee, seen + (comp,))
            memo[comp] = frozenset(found - {"unscoped"})
        return memo[comp]

    names, inside, containers = {}, {}, set()
    for instrs in comps.values():
        for name, line in instrs:
            names[name] = _op_name(line)
            callees = _CALLS.findall(line)
            if callees:
                inside[name] = frozenset().union(*(held(c) for c in callees))
            if _CONTAINER.search(line.split("=", 1)[1]):
                containers.add(name)
    return {"names": names, "inside": inside, "containers": containers}


def words(op_name: str) -> set:
    """The names in an op_name's stack: scopes, and the functions and
    transformations around them (`transpose(jvp(stage0))/qconv` holds
    transpose, jvp, stage0 and qconv)."""
    return set(_WORD.findall(op_name))


def phase(op_name: str) -> str:
    if not op_name:
        return "unscoped"
    if "transpose(" in op_name:
        return "backward"
    held = words(op_name)
    if "momentum_update" in held:
        return "optimizer"
    if "wire" in held:
        return "wire"
    return "forward"


def layer(op_name: str) -> str:
    """The innermost layer scope in the op_name, or "unscoped"."""
    hits = [w for w in _WORD.findall(op_name) if w in LAYER_SCOPES]
    return hits[-1] if hits else "unscoped"


def stage(op_name: str) -> str | None:
    m = _STAGE.search(op_name)
    return m.group(0) if m else None


def split(devices, module: dict, steps: int = 1) -> dict:
    """Device ms per step, averaged over the devices: by phase, by layer,
    by stage, and `holding`: per layer, the ops whose own op_name or fused
    computation holds that layer, an upper bound where XLA fused a layer
    into another's op.  `devices` as trace.reduce gives them; `module`
    from parse()."""
    names, inside = module["names"], module["inside"]
    out = {"phases": dict.fromkeys(PHASES, 0.0),
           "layers": dict.fromkeys(LAYER_SCOPES + ("unscoped", "mixed"), 0.0),
           "holding": dict.fromkeys(LAYER_SCOPES, 0.0),
           "stages": defaultdict(float)}
    per = 1e3 / 1e9 / steps / len(devices)
    for ops in devices:
        for name, _, _, dur in ops:
            if name in module["containers"]:
                continue
            op = names.get(name, "")
            ms = dur * per
            own = layer(op)
            held = inside.get(name, frozenset()) | ({own} - {"unscoped"})
            out["phases"][phase(op)] += ms
            out["layers"][own] += ms
            if len(held) > 1:
                out["layers"]["mixed"] += ms
            for lay in held:
                out["holding"][lay] += ms
            st = stage(op)
            if st is not None:
                out["stages"][st] += ms
    out["stages"] = dict(sorted(out["stages"].items(),
                                key=lambda kv: int(kv[0][5:])))
    return out


def exposed_share(devices, is_wire) -> float | None:
    """Per chip, the part of the union of the intervals of ops for which
    is_wire(name) holds that no other op overlaps, over that union; in %,
    averaged over the chips that ran such an op."""
    shares = []
    for ops in devices:
        wire = TR._union([(o[2], o[2] + o[3]) for o in ops if is_wire(o[0])])
        if not wire:
            continue
        rest = TR._union([(o[2], o[2] + o[3]) for o in ops
                          if not is_wire(o[0])])
        total = sum(e - s for s, e in wire)
        covered, j = 0.0, 0
        for s, e in wire:
            while j < len(rest) and rest[j][1] <= s:
                j += 1
            k = j
            while k < len(rest) and rest[k][0] < e:
                covered += min(e, rest[k][1]) - max(s, rest[k][0])
                k += 1
        shares.append(100.0 * (total - covered) / total)
    return sum(shares) / len(shares) if shares else None


def metrics(parts: dict, exposed: float | None = None) -> dict:
    """The per-layer numbers under the names PERF.md §3 gives them (ms per
    step; `wire_exposed_share` in %), None where nothing was found."""
    ph, ly = parts["phases"], parts["layers"]
    out = {"forward_ms_per_step": ph["forward"],
           "backward_ms_per_step": ph["backward"],
           "optimizer_ms_per_step": ph["optimizer"],
           "ubn_ms_per_step": ly["ubn"],
           "amax_ms_per_step": ly["amax"],
           "wire_ms_per_step": ph["wire"],
           "wire_exposed_share": exposed}
    return {k: (v if v else None) for k, v in out.items()}


def async_ops(path: str, n_devices: int):
    """Events of the "Async XLA Ops" line of each TPU device plane (where
    the starts of async copies and collectives show), as trace.reduce's op
    tuples (category "other"); [] for a device without that line."""
    from jax.profiler import ProfileData
    found = {}
    for plane in ProfileData.from_file(path).planes:
        m = TR.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= n_devices:
            continue
        found[int(m.group(1))] = [
            (TR.instruction_name(e.name), "other", float(e.start_ns),
             float(e.duration_ns))
            for line in plane.lines if line.name == ASYNC_LINE
            for e in line.events]
    return [found[k] for k in sorted(found)]


def report(workload: str, seed: int, out_dir: str | None = None,
           timed_seconds: float = 10.0) -> dict:
    """Compile the cell's step, time it untraced, trace a window, split."""
    import gzip

    import jax

    from bench import correct as C
    from bench import program, spec
    from bench import run as R

    cell, config, traffic = spec.resolve(workload)
    devs = R.devices_for(cell, require_tpu=True)
    R.use_compile_cache()
    prog = program.Program(config, cell)
    params = C.init_params(config, seed)
    ring = R.traffic_ring(config, traffic, seed, prog)
    t = time.perf_counter()
    compiled = prog.build(params, ring[0])
    compile_s = time.perf_counter() - t
    params, opt = prog.state(params)
    loop = R.Loop(compiled, params, opt, ring)
    loop.run(steps=3)
    n, secs = loop.run(steps=2)     # as run.py's set-up ends
    loop.ahead = max(R.AHEAD_STEPS, math.ceil(R.AHEAD_SECONDS * n / secs))
    gc.collect()
    gc.freeze()
    n, secs = loop.run(seconds=timed_seconds)
    step_s = secs / n
    hlo = compiled.as_text()
    tmp = tempfile.mkdtemp(prefix="bench-layers-")
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            steps, _ = loop.run(steps=max(R.TRACE_MIN_STEPS, int(
                R.TRACE_SECONDS / step_s)))
        jax.profiler.stop_trace()
        path = TR.find_xplane(tmp)
        red = TR.reduce(path, len(devs), TR.categories(hlo))
        starts = async_ops(path, len(devs))
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            shutil.copy(path, os.path.join(out_dir, f"{workload}.xplane.pb"))
            with gzip.open(os.path.join(out_dir, f"{workload}.hlo.txt.gz"),
                           "wt") as f:
                f.write(hlo)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    module = parse(hlo)
    parts = split(red["devices"], module, steps)

    def is_wire(name):
        return phase(module["names"].get(name, "")) == "wire"

    both = [a + b for a, b in zip(red["devices"], starts)]
    return {"workload": workload, "seed": seed,
            "device": {"kind": devs[0].device_kind, "count": len(devs)},
            "compile_s": compile_s, "untraced_step_ms": 1e3 * step_s,
            "traced_step_ms": 1e3 * red["window_s"] / steps,
            "traced_steps": steps,
            "busy_ms_per_step": 1e3 * red["busy_s"] / steps,
            "metrics": metrics(parts, exposed_share(both, is_wire)),
            "breakdown": parts}


def main(argv=None):
    p = argparse.ArgumentParser("bench/layers.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--out", help="write the compiled HLO text and the trace "
                   "here")
    args = p.parse_args(argv)
    print(json.dumps(report(args.workload, args.seed, args.out,
                            args.seconds)), flush=True)


if __name__ == "__main__":
    main()
