"""Median wait from a request's scheduled arrival to the start of the slot
in which the engine claimed a row for it (``Request.admit_slot``)."""
import numpy as np


def read(run):
    v = [run.queue_wait_s(s) for s in run.done() if s.req.admit_slot in run.starts]
    return 1e3 * float(np.median(v)) if v else None
