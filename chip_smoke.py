#!/usr/bin/env python3
"""Chip smoke: serve the paper's inception_net(224) through the compiled
ACETONE plan on a TPU, and check what comes out.

    python3 chip_smoke.py [--seed 0]          # one chip
    python3 chip_smoke.py --chips 4           # the 4-chip plan only

One chip (the default) runs the main path at the paper's full width
(Fig. 10, input 224, batch 1):

1. the device: a TPU whose ``device_kind`` has a ``HardwareSpec``;
2. ``inception_net(224)`` with parameters and inputs from ``--seed``;
3. jitted ``run_sequential``: compile, warm calls, error against a float32
   reference on the host CPU device of this process;
4. the sliced DSH plan for m=1 (``search_slice_factors``), timed as the bare
   ``build_mpmd_executor`` call and as the ``checkpoint=True``
   executor that ``Frontend`` serves, then served through ``Frontend`` +
   ``attach_executor``: every request must run through the compiled
   executor, none may shed, and the audit against references from the same
   chip must be zero-loss.

``--chips 4`` runs only the sliced DSH m=4 plan on four devices, timed as
the same two executors; the ``checkpoint=True`` one, which ``Frontend(m=4)``
compiles, is checked for one worker per device and ``collective-permute``
in its compiled text. Then a few requests are served and audited against
``run_sequential`` on device 0.

Earlier lines report set-up, compile and warm call seconds, errors,
snapshot bytes per request and peak device memory.  The last line is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
check passed; without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

# the float32 references run on the host CPU backend beside the chip
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.codegen import build_mpmd_executor, build_plan, validate_plan  # noqa: E402
from repro.codegen.plan import coalesce_transfer_steps  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import dsh  # noqa: E402
from repro.core.costmodel import hardware_for  # noqa: E402
from repro.models.cnn import inception_net, run_sequential  # noqa: E402
from repro.models.slicing import search_slice_factors, slice_model  # noqa: E402
from repro.serve import Frontend, FrontendConfig, input_pool, poisson_trace  # noqa: E402

INPUT_HW = 224          # paper Fig. 10
POOL = 8                # distinct seeded inputs the requests draw from
REQUESTS_ONE_CHIP = 8   # requests served by the m=1 frontend
REQUESTS_FOUR_CHIPS = 4  # requests served by the m=4 frontend
TIME_UNIT = 1e-6        # DAG / simulated-clock unit (s)
WARM_CALLS = 5
# The plan path is checked against the sequential program on the same chip,
# where both use the chip's own matmul precision.
AUDIT_ATOL = 1e-4       # Frontend.audit's default


def _log(tag: str, **fields) -> None:
    print(f"{tag}: {json.dumps(fields)}", flush=True)


def _max_err(a, b) -> float:
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


def _time_calls(fn, *args):
    """Warm wall seconds per call, each ended by ``block_until_ready``."""
    out, times = None, []
    for _ in range(WARM_CALLS):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return out, times


def _compile(fn, *args):
    t = time.perf_counter()
    fn.lower(*args).compile()
    return time.perf_counter() - t


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


class Checks:
    """Named pass/fail checks; the run fails if any failed."""

    def __init__(self):
        self.failed = []

    def expect(self, name: str, ok: bool, detail) -> None:
        _log("check", name=name, ok=bool(ok), detail=detail)
        if not ok:
            self.failed.append(name)


def sliced_plan(model, hw, m: int):
    """``search_slice_factors`` → ``slice_model`` → DSH ``build_plan`` →
    deep ``validate_plan``: the plan ``Frontend`` serves."""
    t = time.perf_counter()
    factors = search_slice_factors(model, hw, m=m)
    sliced = slice_model(model, factors)
    dag = sliced.to_dag(hw, time_unit=TIME_UNIT)
    plan = coalesce_transfer_steps(build_plan(dsh(dag, m), dag))
    validate_plan(plan, dag, model=sliced, deep=True)
    _log(
        "plan", m=m, tasks=len(sliced.layers), supersteps=len(plan.steps),
        transfers=plan.n_transfers, set_up_s=time.perf_counter() - t,
    )
    return sliced, dag, plan


def serve(sliced, dag, params, hw, devices, pool, refs, n: int, seed: int,
          checks: Checks, tag: str):
    """Serve ``n`` one-row requests through ``Frontend`` with the compiled
    executor attached, and audit them against ``refs``."""
    m = len(devices)
    t = time.perf_counter()
    fe = Frontend(
        sliced, params, dag, m=m, hw=hw, cfg=FrontendConfig(max_rows=1),
        time_unit=TIME_UNIT,
    )
    fe.attach_executor(devices=devices, buckets=(1,))
    set_up = time.perf_counter() - t
    # arrivals at half the plan's service rate and deadlines of 8-24
    # service times: nothing should queue long enough to shed
    trace = poisson_trace(
        n, seed=seed, rate=0.5 / fe.est_service, rows=(1,), pool_size=len(pool),
        deadline=(8.0, 24.0), service=fe.est_service,
    )
    t = time.perf_counter()
    summary = fe.run_trace(trace, pool)
    wall = time.perf_counter() - t
    audit = fe.audit(ref_pool=refs, atol=AUDIT_ATOL)
    snaps = fe.last_snapshot[0]
    _log(
        f"serve_m{m}", set_up_s=set_up,
        wall_s_incl_first_compile=wall, exec_runs=fe.exec_runs,
        submitted=audit["submitted"], completed=audit["completed"],
        shed=audit["shed"], max_err=audit["max_err"],
        snapshot_bytes_per_request=int(snaps.nbytes),
        snapshot_shape=list(snaps.shape), sim_p50_ms=summary["p50_ms"],
    )
    checks.expect(
        f"{tag}_all_compiled",
        fe.exec_runs == audit["completed"] == audit["submitted"] == n
        and audit["shed"] == 0,
        {"exec_runs": fe.exec_runs, "completed": audit["completed"],
         "submitted": audit["submitted"], "shed": audit["shed"]},
    )
    checks.expect(
        f"{tag}_zero_loss", audit["zero_loss"],
        {"max_err": audit["max_err"], "atol": AUDIT_ATOL,
         "diverged": audit["diverged"]},
    )


def set_up(seed: int):
    """The model, its seeded parameters and input pool, the first input and
    the jitted sequential program (not yet compiled)."""
    t = time.perf_counter()
    model = inception_net(INPUT_HW)
    params = model.init_params(jax.random.PRNGKey(seed))
    pool = input_pool(model.layers[0].out_shape, POOL, seed=seed + 1)
    x1 = jnp.asarray(pool[:1])
    seq = jax.jit(lambda p, x: run_sequential(model, p, x))
    _log("set_up", model=model.name, input_hw=INPUT_HW,
         seconds=time.perf_counter() - t)
    return model, params, pool, x1, seq


def _outputs(seq, params, pool) -> np.ndarray:
    """``seq`` on every pool input, one row at a time, stacked."""
    return np.stack([np.asarray(seq(params, pool[k:k + 1]))[0]
                     for k in range(POOL)])


def time_executor(plan, sliced, params, devices, x1, ref, *, checkpoint: bool):
    """Build, compile and time one executor of ``plan`` over
    ``devices``; log its seconds and its error against ``ref`` (the same
    chip's sequential output for ``x1``)."""
    m = len(devices)
    mesh = jax.sharding.Mesh(np.asarray(devices), ("workers",))
    t = time.perf_counter()
    f = build_mpmd_executor(plan, sliced, params, mesh, batch=1,
                            checkpoint=checkpoint)
    build_s = time.perf_counter() - t
    t = time.perf_counter()
    compiled = f.lower(x1).compile()
    compile_s = time.perf_counter() - t
    out, times = _time_calls(f, x1)
    y, snaps = out if checkpoint else (out, None)
    err = _max_err(y[0], ref)
    text = compiled.as_text()
    _log(
        f"executor_m{m}" + ("_checkpoint" if checkpoint else ""),
        build_s=build_s, compile_s=compile_s, warm_call_s=times,
        warm_median_s=statistics.median(times), max_err_vs_chip_seq=err,
        collective_permutes=text.count("collective-permute-start")
        or text.count("collective-permute("),
        snapshot_shape=None if snaps is None else list(snaps.shape),
    )
    return y, snaps, text, err


def one_chip(devices, hw, seed: int, checks: Checks) -> None:
    dev = devices[0]
    cpu = jax.devices("cpu")[0]
    model, params, pool, x1, seq = set_up(seed)

    # 3. the sequential program on the chip, and its float32 CPU reference
    seq_compile = _compile(seq, params, x1)
    _, seq_times = _time_calls(seq, params, x1)
    refs = _outputs(seq, params, pool)
    refs_cpu = _outputs(seq, jax.device_put(params, cpu),
                        jax.device_put(pool, cpu))
    with jax.default_matmul_precision("highest"):
        seq_hi = jax.jit(lambda p, x: run_sequential(model, p, x))
        y_hi = seq_hi(params, x1)
    err_hi = _max_err(y_hi[0], refs_cpu[0])
    _log(
        "sequential", compile_s=seq_compile, warm_call_s=seq_times,
        warm_median_s=statistics.median(seq_times),
        max_err_vs_cpu_f32=_max_err(refs, refs_cpu),
        max_err_highest_vs_cpu_f32=err_hi,
        max_abs_output=float(np.abs(refs_cpu).max()),
    )
    # The TPU's default precision rounds f32 matmul operands to bfloat16,
    # so the default-precision errors against the CPU's float32 are printed,
    # not checked; at "highest" the chip must give the float32 result.
    checks.expect("sequential_highest_matches_cpu_f32", err_hi <= AUDIT_ATOL,
                  {"max_err": err_hi, "atol": AUDIT_ATOL})

    # 4. the sliced m=1 plan: the bare and checkpointing executors, then
    # the frontend
    sliced, dag, plan = sliced_plan(model, hw, 1)
    y, _, _, _ = time_executor(plan, sliced, params, [dev], x1, refs[0],
                               checkpoint=False)
    _log("executor_m1_vs_cpu_f32", max_err=_max_err(y[0], refs_cpu[0]))
    time_executor(plan, sliced, params, [dev], x1, refs[0], checkpoint=True)
    serve(sliced, dag, params, hw, [dev], pool, refs, REQUESTS_ONE_CHIP,
          seed, checks, "serve_m1")
    _log("memory", peak_bytes_in_use=_peak_bytes(dev))


def four_chips(devices, hw, seed: int, checks: Checks) -> None:
    devices = devices[:4]
    model, params, pool, x1, seq = set_up(seed)
    refs = _outputs(seq, params, pool)

    sliced, dag, plan = sliced_plan(model, hw, 4)
    time_executor(plan, sliced, params, devices, x1, refs[0],
                  checkpoint=False)
    # the executor Frontend(m=4) compiles: segmented, with checkpoints
    _, snaps, text, err = time_executor(plan, sliced, params, devices, x1,
                                        refs[0], checkpoint=True)
    shard_devices = [s.device for s in snaps.addressable_shards]
    checks.expect(
        "m4_workers_on_distinct_devices",
        len({d.id for d in shard_devices}) == 4
        and {d.id for d in shard_devices} == {d.id for d in devices},
        [str(d) for d in shard_devices],
    )
    checks.expect("m4_collective_permute", "collective-permute" in text,
                  "collective-permute in compiled text")
    checks.expect("m4_executor_matches_device0", err <= AUDIT_ATOL,
                  {"max_err": err, "atol": AUDIT_ATOL})
    serve(sliced, dag, params, hw, devices, pool, refs, REQUESTS_FOUR_CHIPS,
          seed, checks, "serve_m4")
    _log("memory", peak_bytes_in_use=[_peak_bytes(d) for d in devices])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {d0.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    hw = hardware_for(d0.device_kind)
    cache = enable_compile_cache()
    _log("device", platform=d0.platform, kind=d0.device_kind,
         count=len(devices), hw=hw.name, jax=jax.__version__,
         compile_cache=cache)

    checks = Checks()
    if args.chips == 4:
        four_chips(devices, hw, args.seed, checks)
    else:
        one_chip(devices, hw, args.seed, checks)
    if checks.failed:
        print(f"chip_smoke: failed checks: {checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
