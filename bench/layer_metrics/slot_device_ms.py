"""Device busy time (union of XLA op intervals) per control slot in the
traced segment."""


def read(run):
    n = len(run.traced_slots())
    if run.trace is None or not n:
        return None
    return 1e3 * run.trace["busy_s"] / n
