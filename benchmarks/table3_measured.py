"""Paper Table 3 analogue: measured execution of the generated parallel
program vs the sequential reference.

The paper measures per-layer cycles on a 4-core Keystone II.  Our target
is a TPU pod we don't have, so the *measured* claim we can validate on this
1-core CPU container is the semantic one behind Table 3: the generated
multi-worker program (schedule -> plan -> shard_map MPMD executor) computes
the same function as the sequential code, with bounded orchestration
overhead.  Wall-clock parallel gain is NOT expected here (4 placeholder
devices share one physical core — noted in EXPERIMENTS.md); the WCET-model
gain is validated by table1_wcet.py instead.
"""
from __future__ import annotations

import json
import subprocess
import sys
import os
from typing import Dict, List

_SUB = r"""
import json, time
import jax, jax.numpy as jnp
from repro.models.cnn import inception_net, run_sequential
from repro.core import dsh
from repro.core.costmodel import KEYSTONE_CPU
from repro.codegen import build_plan, build_mpmd_executor

key = jax.random.PRNGKey(0)
model = inception_net(64)
params = model.init_params(key)
x = jax.random.normal(key, (4, 64, 64, 3))
seq = jax.jit(lambda x: run_sequential(model, params, x))
ref = seq(x); ref.block_until_ready()
t0 = time.perf_counter()
for _ in range(5):
    ref = seq(x); ref.block_until_ready()
t_seq = (time.perf_counter() - t0) / 5

dag = model.to_dag(KEYSTONE_CPU, time_unit=1e-6)
plan = build_plan(dsh(dag, 4), dag)
mesh = jax.make_mesh((4,), ("workers",))
f = build_mpmd_executor(plan, model, params, mesh, batch=4)
y = f(x); y.block_until_ready()
t0 = time.perf_counter()
for _ in range(5):
    y = f(x); y.block_until_ready()
t_par = (time.perf_counter() - t0) / 5
err = float(jnp.abs(y - ref).max())
print("JSON:" + json.dumps({
    "t_seq_ms": t_seq * 1e3, "t_par_ms": t_par * 1e3,
    "max_err": err, "n_transfers": plan.n_transfers,
    "supersteps": len(plan.steps),
}))
"""


def run() -> Dict:
    env = dict(os.environ)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(here, "src") + os.pathsep + env.get("PYTHONPATH", "")
    # four fake CPU devices; JAX_PLATFORMS=cpu keeps the child off the
    # accelerator, which the parent process may already hold
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SUB], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    line = [l for l in out.stdout.splitlines() if l.startswith("JSON:")][0]
    return json.loads(line[5:])


def main(argv=None) -> List[Dict]:
    r = run()
    print(f"table3,seq={r['t_seq_ms']:.1f}ms,par4={r['t_par_ms']:.1f}ms,"
          f"maxerr={r['max_err']:.2e},transfers={r['n_transfers']},"
          f"supersteps={r['supersteps']}")
    ok = r["max_err"] < 1e-4
    print(f"table3.parallel_equals_sequential,{'PASS' if ok else 'FAIL'}")
    print("table3.note,1-core container: wall-clock gain not expected; "
          "WCET-model gain validated by table1")
    return [dict(r, bench="table3")]


if __name__ == "__main__":
    main()
