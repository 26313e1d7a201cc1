"""Median over the served requests of the program's own stamps
``first_token_us - admit_us``: from the engine claiming a row for the
request to its first generated token reaching the host (prompt chunks,
activation and the readback that carried the token). Absent where the
program does not stamp its requests."""
import numpy as np


def read(run):
    v = [s.req.first_token_us - s.req.admit_us for s in run.done()
         if getattr(s.req, "first_token_us", None) is not None
         and getattr(s.req, "admit_us", None) is not None]
    return 1e-3 * float(np.median(v)) if v else None
