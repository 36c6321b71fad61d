"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer readers use.

Two things are kept:
  * device op intervals: every event of each TPU device plane's "XLA Ops"
    line, as (instruction name, category, start ns, duration ns);
  * the benchmark's own host spans: events named `bench.*` on the host
    plane (TraceAnnotation from run.py: `bench.window`, `bench.batch`,
    `bench.dispatch`, `bench.wait`).
A device event's name is the HLO instruction's text; the instruction name
before " = " is what the compiled program calls it, and `categories()`
classifies those names from the compiled program's HLO text: "conv" for a
convolution or a fusion whose computation holds one, "kernel:<name>" for a
Pallas kernel (a `tpu_custom_call`), "other" for the rest.

Busy time is the union of a device's op intervals; the window spans the
`bench.window` host span and every device op; idle gaps are the holes in
the union, each put down to the innermost `bench.*` span around its middle.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
MIN_GAP_NS = 1000          # holes shorter than 1 us are not listed as gaps
TOP = 10

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*[({]")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_KERNEL = 'custom_call_target="tpu_custom_call"'


def find_xplane(root: str) -> str:
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {root}")
    return max(paths, key=os.path.getmtime)


def instruction_name(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0].lstrip("%")


def group_name(name: str) -> str:
    """An instruction name without its numeric suffix (fusion.12 -> fusion)."""
    return re.sub(r"(\.\d+)+$", "", name)


def categories(hlo_text: str) -> dict:
    """{instruction name: category} for every instruction of the module."""
    comps, cur = defaultdict(list), None
    for line in hlo_text.splitlines():
        if not line.startswith(" ") and "{" in line and "=" not in \
                line.split("{")[0]:
            m = _COMP.match(line)
            cur = m.group(1) if m else None
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            comps[cur].append((m.group(1), line))

    conv_memo = {}

    def holds_conv(comp, seen=()):
        if comp in conv_memo:
            return conv_memo[comp]
        hit = False
        for _, text in comps.get(comp, ()):
            if " convolution(" in text:
                hit = True
                break
            for callee in _CALLS.findall(text):
                if callee not in seen and holds_conv(callee, seen + (comp,)):
                    hit = True
                    break
            if hit:
                break
        conv_memo[comp] = hit
        return hit

    out = {}
    for comp, instrs in comps.items():
        for name, text in instrs:
            if _KERNEL in text:
                out[name] = "kernel:" + group_name(name)
            elif " convolution(" in text or any(
                    holds_conv(c) for c in _CALLS.findall(text)):
                out[name] = "conv"
            else:
                out[name] = "other"
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(path: str, n_devices: int = 1, cats: dict | None = None) -> dict:
    """Device ops, host spans, busy and idle of the trace at `path`."""
    from jax.profiler import ProfileData
    cats = cats or {}
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < n_devices:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name = instruction_name(e.name)
                    ops.append((name, cats.get(name, "other"),
                                float(e.start_ns), float(e.duration_ns)))
            devices[int(m.group(1))] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    return summarize([devices[k] for k in sorted(devices)], spans)


def summarize(devices, spans) -> dict:
    """Busy/idle and breakdown from device op lists and host spans."""
    if not devices or not any(devices):
        raise RuntimeError("the trace holds no device operations")
    starts = [op[2] for ops in devices for op in ops]
    ends = [op[2] + op[3] for ops in devices for op in ops]
    win = [s for s in spans if s[0] == SPAN_PREFIX + "window"]
    lo = min(starts + [s[1] for s in win])
    hi = max(ends + [s[2] for s in win])
    busy, gaps = [], []
    for ops in devices:
        merged = _union([(o[2], o[2] + o[3]) for o in ops])
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e - s >= MIN_GAP_NS:
                gaps.append((_host_doing(spans, (s + e) / 2), (e - s) / 1e9))
    per_op = defaultdict(float)
    for ops in devices:
        for name, _, _, dur in ops:
            per_op[group_name(name)] += dur / 1e9 / len(devices)
    return {
        "devices": devices,
        "spans": spans,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                per_op.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[k, v] for k, v in sorted(
                gaps, key=lambda g: -g[1])[:TOP]],
        },
    }


def _host_doing(spans, t) -> str:
    inner = [s for s in spans if s[1] <= t <= s[2]]
    if not inner:
        return "none"
    return min(inner, key=lambda s: s[2] - s[1])[0]


def op_seconds(ctx, pred) -> float | None:
    """Device seconds of the ops matching pred(name, category), averaged
    over the devices; None where no op matches."""
    total, hit = 0.0, False
    for ops in ctx["devices"]:
        for name, cat, _, dur in ops:
            if pred(name, cat):
                total += dur / 1e9
                hit = True
    return total / len(ctx["devices"]) if hit else None
