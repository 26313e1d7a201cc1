"""Median over the served requests of the gap between output tokens:
(last token's landing - first token's landing) / (tokens - 1)."""
import numpy as np


def read(run):
    v = [t for t in (run.tpot_s(s) for s in run.done()) if t is not None]
    return 1e3 * float(np.median(v)) if v else None
