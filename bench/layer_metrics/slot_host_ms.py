"""Host work per control slot that dispatched device work, from the
program's own spans (``run.ring``, the recorder's events): the median over
such slots in the window of a ``serve.slot`` span less the ``engine.wait``
spans inside it, in which the host only waited on the device. (The median,
since the traced run starts and stops the profiler inside two of those
slots.) Absent without a ring."""
import numpy as np


def read(run):
    ring = getattr(run, "ring", None)
    if not ring:
        return None
    end = run.t0 + run.seconds
    keep = {t for t in run.dispatching if run.t0 <= run.starts[t] < end}
    waits = [(e["ts"], e["ts"] + e["dur"]) for e in ring
             if e["kind"] == "engine.wait"]
    host = []
    for e in ring:
        if e["kind"] != "serve.slot" or e["slot"] not in keep:
            continue
        lo, hi = e["ts"], e["ts"] + e["dur"]
        host.append(e["dur"] - sum(b - a for a, b in waits if lo <= a and b <= hi))
    return 1e-3 * float(np.median(host)) if host else None
