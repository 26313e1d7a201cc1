"""Median time to first token over the served requests: from a request's
scheduled arrival to the host time at which the readback of the slot that
computed its first token landed. (A chat cell serves about 35 requests in
its 51 s window: a tail such as p90 would rest on its top three.)"""
import numpy as np


def read(run):
    v = [run.ttft_s(s) for s in run.done()]
    return 1e3 * float(np.median(v)) if v else None
