"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData`` alone. From the device planes
(``/device:TPU:<n>``): busy seconds as the union of the intervals in
which a compiled program ran (line ``XLA Modules``; averaged over the
chips used), device seconds per op (line ``XLA Ops``) grouped as
``<jit module>/<HLO instruction>``, and the seconds of each Pallas kernel
(a ``tpu_custom_call``, keyed by its instruction name, which is the
name of the jitted function that calls the kernel, e.g.
``gotoh_forward_pallas``). From the host plane: the self time of each
``repro.obs`` span (the spans reach the trace as ``TraceAnnotation``
events; self time is a span's time less that of the spans inside it on
the same thread), and the longest device-idle gaps, each named by the
innermost span the host was in at its middle.
"""
from __future__ import annotations

import bisect
import glob
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
TOP = 10
# program spans whose work runs on the device, and the slack allowed
# between such a span's start and the last device event recorded
DEVICE_SPANS = ("map1", "serve.batch", "tree.distance")
CUT_MARGIN_NS = 100_000_000


def union_seconds(intervals) -> tuple[float, list]:
    """Total length of the union of (start, end) intervals (ns in, s out),
    and the merged intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, merged


def op_name(event_name: str) -> str:
    """``%gotoh_forward_pallas.1 = (...) custom-call(...)`` ->
    ``gotoh_forward_pallas``: the HLO instruction without its number."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def is_kernel(event_name: str) -> bool:
    """A Pallas (Mosaic) kernel is a ``tpu_custom_call`` in the HLO."""
    return 'custom_call_target="tpu_custom_call"' in event_name


def module_name(event_name: str) -> str:
    """``jit_match_valid_pallas(1579...)`` -> ``jit_match_valid_pallas``."""
    return event_name.split("(", 1)[0]


def device_lines(plane):
    ops, modules = [], []
    for line in plane.lines:
        if line.name == OPS_LINE:
            ops = list(line.events)
        elif line.name == MODULES_LINE:
            modules = list(line.events)
    return ops, modules


def device_record_cut(last_device_ns, host_events) -> bool:
    """Whether a program span with device work starts after the last
    recorded device event (by more than ``CUT_MARGIN_NS``)."""
    return any(name in DEVICE_SPANS and start > last_device_ns + CUT_MARGIN_NS
               for _, name, start, _ in host_events)


def span_self_seconds(host_events) -> dict:
    """``host_events``: (thread, name, start_ns, end_ns) of annotations.
    Self seconds per name: duration less the nested spans' durations."""
    out = defaultdict(float)
    by_thread = defaultdict(list)
    for th, name, s, e in host_events:
        by_thread[th].append((s, -e, name))
    for evs in by_thread.values():
        evs.sort()
        stack = []          # (end, name, child_ns)
        for s, neg_e, name in evs:
            e = -neg_e
            while stack and stack[-1][0] <= s:
                end, nm, child, st = stack.pop()
                out[nm] += (end - st - child) / 1e9
                if stack:
                    stack[-1][2] += end - st
            if stack and e > stack[-1][0]:
                # overlaps the open span without nesting in it (spans of
                # other threads on one line): counted whole, no child
                out[name] += (e - s) / 1e9
                continue
            stack.append([e, name, 0, s])
        while stack:
            end, nm, child, st = stack.pop()
            out[nm] += (end - st - child) / 1e9
            if stack:
                stack[-1][2] += end - st
    return dict(out)


def reduce(pd, *, chips: int, window_s: float, span_names=None) -> dict:
    """Summary of one ``ProfileData``; ``span_names`` limits the host
    events read to the program's spans (None: every host event)."""
    planes = sorted((p for p in pd.planes
                     if p.name.startswith(DEVICE_PREFIX)),
                    key=lambda p: int(p.name[len(DEVICE_PREFIX):]
                                      .split()[0] or 0))[:chips]
    busy, busy_ops, n_ops = [], [], 0
    ops = defaultdict(float)
    kernels = defaultdict(float)
    intervals0 = []
    names: dict = {}            # event name -> (op name, is a kernel)
    for k, plane in enumerate(planes):
        events, modules = device_lines(plane)
        mods = sorted((m.start_ns, m.start_ns + m.duration_ns,
                       module_name(m.name)) for m in modules)
        starts = [m[0] for m in mods]
        op_spans = []
        for ev in events:
            s, d = ev.start_ns, ev.duration_ns
            op_spans.append((s, s + d))
            name = ev.name
            if name not in names:
                names[name] = (op_name(name), is_kernel(name))
            op, kern = names[name]
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][2] if i >= 0 and s <= mods[i][1] else "?"
            ops[f"{module}/{op}"] += d / 1e9 / len(planes)
            if kern:
                kernels[op] += d / 1e9 / len(planes)
        n_ops += len(events)
        total, merged = union_seconds([(m[0], m[1]) for m in mods])
        busy.append(total)
        busy_ops.append(union_seconds(op_spans)[0])
        if k == 0:
            intervals0 = merged
    busy_s = sum(busy) / len(busy) if busy else 0.0
    host = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if span_names is None or ev.name in span_names:
                    host.append((line.name, ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
    # The profiler keeps a bounded number of device events; past it the
    # device lines simply end. A program span that starts after the last
    # recorded device event means the device record was cut there: the
    # traced window is then the part of it the device record covers.
    last_ns = intervals0[-1][1] if intervals0 else 0
    truncated = device_record_cut(last_ns, host)
    if truncated:
        window_s = last_ns / 1e9
    gaps = sorted((((s1 - e0) / 1e9, (e0 + s1) / 2)
                   for (_, e0), (s1, _) in zip(intervals0, intervals0[1:])),
                  reverse=True)[:TOP]
    labelled = []
    for length, mid in gaps:
        inner = [h for h in host if h[2] <= mid <= h[3]]
        labelled.append([min(inner, key=lambda h: h[3] - h[2])[1]
                         if inner else "no span", length])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_s, "window_s": window_s, "truncated": truncated,
            "busy_ops_s": sum(busy_ops) / len(busy_ops) if busy_ops else 0.0,
            "op_events": n_ops,
            "devices": len(planes), "ops": dict(ops),
            "kernels": dict(kernels),
            "span_self_s": span_self_seconds(host),
            "breakdown": {"device_ops": [[n, s] for n, s in top_ops],
                          "idle_gaps": labelled}}


def find_xplane(trace_dir) -> str:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir, *, chips: int, window_s: float,
               span_names=None) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    return reduce(pd, chips=chips, window_s=window_s, span_names=span_names)

