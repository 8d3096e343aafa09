"""Benchmark aggregator: one module per paper table/figure + roofline.

``PYTHONPATH=src python -m benchmarks.run [--quick]``

Prints CSV-ish lines ``bench,...`` plus PASS/FAIL lines for each paper
claim being validated.  Exit code is non-zero if any claim FAILs.
"""
from __future__ import annotations

import contextlib
import io
import sys
import time


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    quick = "--quick" in argv
    failures = 0
    t00 = time.time()

    from benchmarks import fig7_heuristics, fig8_cp, table1_wcet, roofline

    sections = [
        ("fig7 (ISH/DSH heuristics)", fig7_heuristics.main),
        ("fig8 (CP encodings)", fig8_cp.main),
        ("table1 (WCET schedule, paper's OTAWA bounds)", table1_wcet.main),
        ("roofline (dry-run artifacts)", roofline.main),
    ]
    if quick:
        sections = [s for s in sections if "fig8" not in s[0]]

    for name, fn in sections:
        print(f"# ==== {name} ====", flush=True)
        t0 = time.time()
        buf = io.StringIO()
        try:
            with contextlib.redirect_stdout(buf):
                fn()
            out = buf.getvalue()
            print(out, end="")
            failures += out.count(",FAIL")
        except Exception as e:
            print(f"{name},ERROR,{type(e).__name__}: {e}")
            failures += 1
        print(f"# ({time.time()-t0:.1f}s)", flush=True)

    print(f"# total {time.time()-t00:.1f}s, failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
