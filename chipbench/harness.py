"""One run of one benchmark cell: set-up, warm-up, the measured window, the
comparison with the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix, entry, loop or
metric lives in a file of its own under this directory and is found by the
name that ``BENCHMARK.json`` or the cell file gives:

* ``cells/<workload>.json``: config, traffic, entry, m, chips and limits;
* ``configs/<config>.json``: the model as it is run (see ``reference.py``);
* ``traffic/<traffic>.json``: loop, clients, rows, pool;
* ``loops/<loop>.py``: ``run(entry, pool, seconds, traffic, ctx, first)``;
* ``entries/<entry>.py``: ``build(ctx, params) -> Entry`` with
  ``infer(rid, idx, pool) -> host outputs`` and ``close()``;
* ``metrics/<metric>.py``: ``read(ctx) -> float | None``.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
import traceback
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench import reference, tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WARM_REQUESTS = 3       # the first compiles; the rest settle dispatch caches
TRACE_SECONDS = 2.0     # longest traced window: device traces grow fast


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str) -> dict:
    """The cell file, checked against the workload's entry in
    ``BENCHMARK.json``."""
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = load_json("cells", workload)
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise ValueError(f"cells/{workload}.json has {key}={cell[key]!r}, "
                             f"BENCHMARK.json {entry[key]!r}")
    return dict(cell, name=workload)


def metrics_for(bench: dict, workload: str, traced: bool) -> List[dict]:
    """The metrics this cell reports: end-to-end untraced, per-layer traced."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


class Context:
    """What one run knows: its cell, configuration, traffic, devices, host
    spans and, once the window has closed, its records and trace."""

    def __init__(self, cell: dict, cfg: dict, traffic: dict, devices, hw_kind: str):
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.devices = list(devices)
        self.hw_kind = hw_kind
        self.spans: Dict[str, float] = {}
        self.tracing = False
        self.setup_s: Optional[float] = None
        self.latencies: List[float] = []
        self.window_s: Optional[float] = None
        self.completed = 0
        self.trace: Optional[dict] = None
        self.traced_completed = 0
        self.flops_per_inference = reference.flops_per_inference(cfg)
        self.peak_flops: Optional[float] = None

    @contextlib.contextmanager
    def span(self, name: str):
        """A set-up span: host seconds under ``spans[name]``."""
        t = time.perf_counter()
        try:
            yield
        finally:
            self.spans[name] = time.perf_counter() - t

    def annotate(self, name: str):
        """A request span in the profiler's trace, only while tracing."""
        if self.tracing:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


def peak_flops(device_kind: str) -> float:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peak for device kind {device_kind!r} in peaks.json")
    return float(peaks[device_kind]["flops_per_s"])


def _compile_counter():
    """A list that grows by one for every backend compile (or cache load)."""
    events: List[float] = []

    def listen(event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    return events


def _peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1)) for d in devices]
    return max(peaks)


def _log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def run_cell(bench: dict, cell: dict, cfg: dict, traffic: dict, seed: int,
             seconds: float, traced: bool, devices, t_start: float,
             hw_kind: Optional[str] = None) -> dict:
    """One run of ``cell``; returns the result line's object.

    ``devices`` are the chips the cell may use (the first ``cell["chips"]``
    of them run it). The reference runs on the first after the window.
    The program prices its plan for ``hw_kind``, by default the chips' own
    ``device_kind``; ``t_start`` is when the process started.
    """
    devices = list(devices)[:cell["chips"]]
    ctx = Context(cell, cfg, traffic, devices, hw_kind or devices[0].device_kind)
    compiles = _compile_counter()
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])

    params = reference.make_params(cfg, seed, device=devices[0])
    jax.block_until_ready(params)
    pool = reference.input_pool(cfg, traffic["pool"], seed)
    entry = load_module("entries", cell["entry"]).build(ctx, params)
    loop = load_module("loops", traffic["loop"])

    with ctx.span("compile"):
        entry.infer(-1, 0, pool)
    for j in range(1, WARM_REQUESTS):
        entry.infer(-1 - j, j % len(pool), pool)
    ctx.setup_s = time.perf_counter() - t_start

    n_compiles = len(compiles)
    if traced:
        ctx.peak_flops = peak_flops(devices[0].device_kind)
        window = min(seconds, TRACE_SECONDS)
        with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the harness's own spans, little else
            ctx.tracing = True
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("window"):
                    rec = loop.run(entry, pool, window, traffic, ctx)
            finally:
                jax.profiler.stop_trace()
                ctx.tracing = False
            ctx.trace = tracereduce.reduce(tracereduce.load(tdir, [d.id for d in devices]))
        ctx.traced_completed = len(rec["latencies"])
    else:
        rec = loop.run(entry, pool, seconds, traffic, ctx)
    in_window = len(compiles) - n_compiles
    _log(f"compilations_in_window: {in_window}")
    if rec["latencies"]:
        lat = np.asarray(rec["latencies"])
        med = float(np.median(lat))
        # a closed loop starts each request as the one before ends, so the
        # running sum of latencies places a request in the window
        starts = np.cumsum(lat) - lat
        slow = np.flatnonzero(lat > 4 * med)
        longest = slow[np.argsort(lat[slow])[::-1][:5]]
        _log(f"latency_max_ms: {lat.max() * 1e3}")
        _log(f"slow_requests: {len(slow)} over 4x the median, "
             f"{float((lat[slow] - med).sum())} s beyond it; the longest "
             f"{[round(float(lat[i]) * 1e3, 1) for i in longest]} ms at "
             f"{[round(float(starts[i]), 2) for i in longest]} s into the window")
    ctx.latencies = rec["latencies"]
    ctx.window_s = rec["window_s"]
    ctx.completed = len(rec["latencies"])
    memory_peak = _peak_bytes(devices)
    failed = rec["failed"] + entry.off_path()

    # the comparison: every answer of the window against the reference
    entry.close()
    del entry
    gc.collect()
    idx = np.asarray(rec["pool_idx"], np.int64)
    outs = np.stack(rec["outputs"]) if rec["outputs"] else np.zeros((0,))
    used = np.unique(idx)
    refs = np.zeros((len(pool),) + outs.shape[1:], np.float32)
    if len(used):
        host = reference.outputs(cfg, params, pool[used], device=devices[0])
        refs[used] = host.reshape((len(used),) + outs.shape[1:])
    errs = reference.compare(outs, refs[idx])
    limits = cell["limits"]
    checks = {k: (errs[k], limits[k]) for k in ("max_rel_err", "rms_rel_err")}
    checks["failed"] = (failed, 0)
    correct = ctx.completed > 0 and all(v <= lim for v, lim in checks.values())
    for name, (v, lim) in checks.items():
        _log(f"check {name}: {v!r} limit {lim!r}")

    metrics = {}
    for m in metrics_for(bench, cell["name"], traced):
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {
        "correct": bool(correct),
        "attempted": ctx.completed + rec["failed"],
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if traced:
        t = ctx.trace
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {"device_ops": t["top_ops"], "idle_gaps": t["idle_gaps"]}
    # JSON has no infinity: a run with no or non-finite answers reads the
    # largest float instead
    result["checks"] = {k: {"value": min(v, sys.float_info.max), "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main_run(workload: str, seed: int, seconds: float, traced: bool,
             t_start: float) -> int:
    """The command's body once the chip has been found (see ``run.py``)."""
    bench = load_benchmark()
    cell = load_cell(bench, workload)
    cfg = reference.load_config(cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    devices = jax.devices()
    try:
        result = run_cell(bench, cell, cfg, traffic, seed, seconds, traced,
                          devices, t_start)
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(result), flush=True)
    return 0
