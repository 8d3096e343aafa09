"""Dry-run lowering + roofline analysis (no jax-device side effects).

Importable from tests and benchmarks; the 512-device env setup lives only in
``repro.launch.dryrun`` (the CLI).  See that module's docstring.
"""

import json
import os
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.configs import SHAPES, get_config, list_archs, runnable_cells, skip_reason
from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch.mesh import make_production_mesh, mesh_shape_dict
from repro.launch.specs import cell_shardings, input_specs, microbatches_for
from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig
from repro.serve.engine import ServeConfig, make_decode_step, make_prefill_step
from repro.train.loop import TrainConfig, make_train_step

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

# v5e per-chip constants (roofline brief)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
HBM_CAP = 16 * 2**30

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output-operand bytes of every collective in the partitioned HLO."""
    out = {op: 0.0 for op in _COLL_OPS}
    for line in hlo_text.splitlines():
        stripped = line.lstrip()
        if "=" not in stripped:
            continue
        for op in _COLL_OPS:
            tok = f" {op}("
            idx = stripped.find(tok)
            if idx < 0:
                continue
            lhs = stripped[:idx]
            nbytes = 0.0
            for (dt, dims) in _SHAPE_RE.findall(lhs):
                if dt not in _DTYPE_BYTES:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                nbytes += n * _DTYPE_BYTES[dt]
            out[op] += nbytes
            break
    return out


def model_flops(cfg: ArchConfig, shape: ShapeSpec) -> float:
    """MODEL_FLOPS = 6·N_active·tokens (train) / 2·N_active·tokens (fwd)."""
    _total, active = cfg.param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * active * tokens


def lower_cell(cfg: ArchConfig, shape: ShapeSpec, mesh: jax.sharding.Mesh,
               moe_impl: str = "einsum", microbatches: Optional[int] = None,
               bf16_moments: Optional[bool] = None):
    """Build + lower the cell's step; returns (lowered, meta)."""
    cs = cell_shardings(cfg, shape, mesh)
    if shape.kind == "train":
        mb = microbatches if microbatches is not None else microbatches_for(cfg, shape, mesh)
        big = cfg.param_count()[0] > 2e11
        tcfg = TrainConfig(
            microbatches=mb, remat=True, moe_impl=moe_impl,
            optim=AdamWConfig(bf16_moments=bf16_moments if bf16_moments is not None else big),
        )
        if tcfg.optim.bf16_moments:
            # moments dtype follows the optimizer config
            import jax.numpy as jnp
            m, v = cs.abstract_args[1]["m"], cs.abstract_args[1]["v"]
            cs.abstract_args[1]["m"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), m)
            cs.abstract_args[1]["v"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), v)
        step = make_train_step(cfg, tcfg, grad_shardings=cs.in_shardings[1]["m"])
        meta = {"microbatches": mb, "bf16_moments": tcfg.optim.bf16_moments}
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, ServeConfig(max_seq=shape.seq_len,
                                                  moe_impl=moe_impl))
        meta = {}
    else:
        step = make_decode_step(cfg, ServeConfig(max_seq=shape.seq_len,
                                                 moe_impl=moe_impl))
        meta = {}
    jitted = jax.jit(
        step,
        in_shardings=cs.in_shardings,
        out_shardings=cs.out_shardings,
        donate_argnums=cs.donate_argnums,
    )
    from repro.models import flags

    with mesh, flags.mxu_einsums():  # TPU-target matmul dtypes (§Perf i3)
        lowered = jitted.lower(*cs.abstract_args)
    return lowered, meta


def analyze_cell(cfg: ArchConfig, shape: ShapeSpec, mesh: jax.sharding.Mesh,
                 **kw) -> Dict[str, Any]:
    t0 = time.monotonic()
    lowered, meta = lower_cell(cfg, shape, mesh, **kw)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    with mesh:
        compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    n_dev = mesh.devices.size
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
        }
    except Exception:
        mem = {}
    coll = collective_bytes(compiled.as_text())
    coll_total = sum(coll.values())

    # --- roofline terms (per chip; cost_analysis is per-partition) -------- #
    compute_t = flops / PEAK_FLOPS
    memory_t = bytes_acc / HBM_BW
    collective_t = coll_total / ICI_BW
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": collective_t}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    arg_b = mem.get("argument_bytes") or 0
    tmp_b = mem.get("temp_bytes") or 0
    out_b = mem.get("output_bytes") or 0
    # donated buffers alias arguments; peak ≈ args + temps
    hbm = arg_b + tmp_b

    return {
        "arch": cfg.name,
        "shape": shape.name,
        "kind": shape.kind,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": n_dev,
        "meta": meta,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": bytes_acc,
        "collective_bytes_per_dev": coll,
        "collective_total_per_dev": coll_total,
        "memory": mem,
        "hbm_per_dev_bytes": hbm,
        "hbm_ok": bool(hbm <= HBM_CAP),
        "roofline": terms,
        "dominant": dominant,
        "model_flops_total": mf,
        "model_flops_per_dev": mf / n_dev,
        "useful_flops_ratio": (mf / n_dev) / flops if flops else None,
        "step_time_bound_s": max(terms.values()),
    }


def attach_analytic(rec: Dict[str, Any], cfg: ArchConfig, shape: ShapeSpec,
                    mesh_shape: Dict[str, int], moe_impl: str = "einsum") -> None:
    """Add the analytic roofline terms (see roofline_model.py for why the
    compiled aggregate cannot be used directly on scanned programs)."""
    from repro.launch.roofline_model import analytic_terms

    meta = rec.get("meta", {})
    ana = analytic_terms(
        cfg, shape, mesh_shape, moe_impl=meta.get("moe_impl", moe_impl),
        microbatches=meta.get("microbatches"),
        bf16_moments=meta.get("bf16_moments"),
    )
    rec["analytic"] = ana
    # analytic terms become the headline roofline; the raw compiled-aggregate
    # terms stay under `compiled_aggregate` for reference
    rec["compiled_aggregate"] = {
        "roofline": rec.get("roofline"), "dominant": rec.get("dominant"),
        "note": "XLA cost_analysis counts while-loop bodies once; see "
                "roofline_model.py",
    }
    rec["roofline"] = ana["roofline"]
    rec["dominant"] = ana["dominant"]
    rec["useful_flops_ratio"] = ana["useful_flops_ratio"]
    rec["model_flops_per_dev"] = ana["model_flops_per_dev"]
    rec["roofline_fraction"] = ana["roofline_fraction"]
    rec["step_time_bound_s"] = ana["step_time_bound_s"]


def probe_config(cfg: ArchConfig) -> ArchConfig:
    """Shallow (1-2 unit) variant of an arch for unrolled probe lowering."""
    import dataclasses as dc

    if cfg.hybrid is not None:
        return dc.replace(cfg, n_layers=cfg.hybrid.attn_period)
    if cfg.moe is not None and cfg.moe.first_dense:
        return dc.replace(cfg, n_layers=cfg.moe.first_dense + 1)
    return dc.replace(cfg, n_layers=2)


def validate_probe(arch: str, kind: str, mesh: jax.sharding.Mesh,
                   seq: int = 1024, batch: int = 16,
                   moe_impl: str = "einsum") -> Dict[str, Any]:
    """Compare analytic terms vs compiled cost_analysis on a small module
    with EVERY scan unrolled (where XLA's counts are exact)."""
    from repro.configs import get_config
    from repro.launch.roofline_model import analytic_terms
    from repro.models import flags

    cfg = probe_config(get_config(arch))
    shape = ShapeSpec(f"probe_{kind}", kind, seq, batch)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    with flags.unrolled_scans():
        lowered, meta = lower_cell(cfg, shape, mesh, moe_impl=moe_impl,
                                   microbatches=1, bf16_moments=False)
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    coll = sum(collective_bytes(compiled.as_text()).values())
    ana = analytic_terms(cfg, shape, mesh_shape, moe_impl=moe_impl,
                         microbatches=1, bf16_moments=False)
    return {
        "arch": arch, "kind": kind, "seq": seq, "batch": batch,
        "measured": {"flops": flops, "bytes": bytes_acc, "coll": coll},
        "analytic": {"flops": ana["flops_per_dev"],
                     "bytes": ana["bytes_per_dev"],
                     "coll": ana["coll_per_dev"]},
        "ratio": {
            "flops": ana["flops_per_dev"] / flops if flops else None,
            "bytes": ana["bytes_per_dev"] / bytes_acc if bytes_acc else None,
            "coll": ana["coll_per_dev"] / coll if coll else None,
        },
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             force: bool = False, **kw) -> Optional[Dict[str, Any]]:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{mesh_kind}"
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    cfg = get_config(arch)
    reason = skip_reason(cfg, shape_name)
    if reason is not None:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "skipped": reason}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    try:
        rec = analyze_cell(cfg, SHAPES[shape_name], mesh, **kw)
        attach_analytic(rec, cfg, SHAPES[shape_name],
                        dict(zip(mesh.axis_names, mesh.devices.shape)),
                        moe_impl=kw.get("moe_impl", "einsum"))
    except Exception as e:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        raise
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


