"""Entry ``frontend_plan``: the ACETONE plan path as a user serves it.

Set-up calls ``search_slice_factors`` -> ``slice_model`` -> ``to_dag`` and
builds ``Frontend`` (DSH schedule, ``build_plan``, deep ``validate_plan``),
all under the ``plan`` span, then routes every request through the compiled
``checkpoint=True`` segmented executor (``attach_executor``). One request is
``Frontend.submit`` + ``Frontend.step``; its output is on the host when
``step`` returns.
"""
from __future__ import annotations

import math

TIME_UNIT = 1e-6  # the DAG's simulated-clock unit (s), as the program's tools use


class FrontendPlan:
    def __init__(self, ctx, params):
        from repro.core.costmodel import hardware_for
        from repro.models import cnn
        from repro.models.slicing import search_slice_factors, slice_model
        from repro.serve import Frontend, FrontendConfig
        from repro.serve.trace import TraceRequest

        from chipbench import reference

        cfg, cell, traffic = ctx.cfg, ctx.cell, ctx.traffic
        prog = cfg["program"]
        model = getattr(cnn, prog["builder"])(**prog["kwargs"])
        reference.check_program_matches(cfg, model.layers)
        m = cell["m"]
        self.rows = traffic["rows"]
        self.ctx = ctx
        self.Request = TraceRequest
        hw = hardware_for(ctx.hw_kind)
        with ctx.span("plan"):
            factors = search_slice_factors(model, hw, m=m)
            sliced = slice_model(model, factors)
            dag = sliced.to_dag(hw, time_unit=TIME_UNIT)
            self.fe = Frontend(sliced, params, dag, m=m, hw=hw,
                               cfg=FrontendConfig(max_rows=self.rows),
                               time_unit=TIME_UNIT)
        self.fe.attach_executor(devices=ctx.devices[:m], buckets=(self.rows,))
        self.served = 0

    def infer(self, rid, idx, pool):
        fe, ann = self.fe, self.ctx.annotate
        with ann("send"):
            req = self.Request(rid, fe.now, self.rows, idx, math.inf)
        with ann("submit"):
            r = fe.submit(req, pool)
        if r.status != "queued":
            raise RuntimeError(f"request {rid} not admitted: {r.status} {r.shed_reason}")
        with ann("step"):
            fe.step()
        with ann("fetch"):
            if r.status != "done":
                raise RuntimeError(f"request {rid} ended {r.status}")
            y = r.output
        self.served += 1
        return y

    def off_path(self) -> int:
        """Requests served that did not run the compiled executor."""
        return self.served - self.fe.exec_runs

    def close(self):
        self.fe = None


def build(ctx, params):
    return FrontendPlan(ctx, params)
