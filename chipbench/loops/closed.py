"""Loop ``closed``: one client at a time sends a request and waits for its
output on the host before it sends the next. Request ``k`` of the window
reads pool entry ``k mod pool``. A request that raises counts as failed.
"""
from __future__ import annotations

import sys
import time
import traceback


def run(entry, pool, seconds, traffic, ctx):
    if traffic["clients"] != 1 or traffic["rows"] != 1:
        raise ValueError("loop 'closed' drives one client at one row")
    n = len(pool)
    lat, outs, idxs, failed = [], [], [], 0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    last = t0
    k = 0
    while last < t_end:
        idx = k % n
        t = time.perf_counter()
        try:
            y = entry.infer(k, idx, pool)
        except Exception:
            if not failed:
                traceback.print_exc(file=sys.stderr)
            failed += 1
            y = None
        last = time.perf_counter()
        if y is not None:
            lat.append(last - t)
            outs.append(y)
            idxs.append(idx)
        k += 1
    return {"latencies": lat, "outputs": outs, "pool_idx": idxs,
            "failed": failed, "window_s": last - t0}
