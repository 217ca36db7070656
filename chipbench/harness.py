"""The shared run of one cell: set-up, window, trace, checks, result line.

Everything that belongs to one configuration, traffic mix, entry point
or per-layer metric lives in a file of its own and is found here by the
name ``BENCHMARK.json`` gives it:

  configs/<config>.json     the deployment's shapes, scoring, limits
  traffic/<mix>.json        how its inputs are sent (data only)
  entries/<entry>.py        how one kind of entry point is driven
  metrics/<metric>.py       one per-layer metric: ``read(ctx)``
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import NamedTuple, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in e2e_names]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer)


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_tpu(chips: int) -> None:
    """The chip or nothing: no CPU fallback, no result line."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (backend {backend!r})")
    if len(jax.devices()) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"sees {len(jax.devices())}")


def configure_jax() -> None:
    """The persistent compile cache at one fixed path in the checkout,
    every compiled program kept, however quick its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the directory is this checkout's own: nothing is evicted from it
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Programs handed to the backend (compiled, or loaded from the
    persistent cache) and persistent-cache loads, as JAX reports them."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_loads += 1

    def read(self) -> tuple[int, int]:
        return self.compiles, self.cache_loads


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> Optional[int]:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def span_seconds(spans, t0: float, t1: float) -> dict:
    """Total seconds of each span name that closed inside [t0, t1]."""
    out: dict = {}
    for s in spans:
        if s.t0 >= t0 and s.t1 <= t1:
            out[s.name] = out.get(s.name, 0.0) + s.duration
    return out


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, chips_check: bool = True,
             out=sys.stdout, err=sys.stderr) -> dict:
    """One run: set-up, the measured window, the checks. Returns the
    result line's object (also printed, last, to ``out``)."""
    if chips_check:
        require_tpu(cell.chips)
    import jax
    configure_jax()
    counter = CompileCounter()
    from repro.obs import trace as obs_trace

    work = Path(tempfile.mkdtemp(prefix="chipbench_"))
    entry = load_module("entries", cell.config["entry"]).Entry(
        cell.config, cell.traffic, seed=seed, workdir=work,
        chips=cell.chips)
    try:
        entry.setup(seconds)
        setup_s = time.perf_counter() - t_start
        trace_dir = work / "trace"
        if trace:
            obs_trace.enable_jax_annotations(True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        comp0 = counter.read()
        t0 = time.perf_counter()
        win = entry.window(seconds)
        t1 = time.perf_counter()
        comp1 = counter.read()
        spans = span_seconds(obs_trace.TRACER.spans(), t0, t1)
        summary = None
        if trace:
            jax.profiler.stop_trace()
            obs_trace.enable_jax_annotations(False)
            import trace_reduce
            summary = trace_reduce.reduce_dir(
                trace_dir, chips=cell.chips, window_s=t1 - t0,
                span_names=set(spans))
        ctx = {"work": win["work"], "spans": spans, "trace": summary}
        loads = comp1[1] - comp0[1]
        print(f"window: {t1 - t0:.3f} s; compilations inside the window: "
              f"{comp1[0] - comp0[0] - loads} (programs loaded from the "
              f"persistent cache: {loads}); {win['note']}", file=out,
              flush=True)
        if trace:
            cut = (" (the device record was cut short: the traced window "
                   "is the part it covers)" if summary["truncated"] else "")
            print(f"trace{cut}: busy {summary['busy_s']:.6f} s (programs), "
                  f"{summary['busy_ops_s']:.6f} s (ops) of "
                  f"{summary['window_s']:.6f} s; {summary['op_events']} op "
                  f"events; kernels {summary['kernels']}; span self-time "
                  f"{summary['span_self_s']}", file=out, flush=True)
        peak = memory_peak(cell.chips)
        info = device_info()
        entry.release()
        checks = entry.check()
    finally:
        entry.close()
        shutil.rmtree(work, ignore_errors=True)

    correct = bool(checks) and all(c.ok for c in checks) and \
        win["attempted"] > 0
    device = dict(info, memory_peak_bytes=peak)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    else:
        values = dict(win["e2e"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = summary["breakdown"]
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result


@contextlib.contextmanager
def quiet_stdout():
    """Entry points print their reports; the result line must be last."""
    with open(os.devnull, "w") as sink, contextlib.redirect_stdout(sink):
        yield
