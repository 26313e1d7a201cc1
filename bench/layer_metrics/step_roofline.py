"""The least time the chip could take for the traced segment's steps, each
step bound by max(FLOPs / peak, bytes / HBM bandwidth) (``counts.py``), over
device busy time."""
import counts


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    work = run.traced_work()
    if not work:
        return None
    least = sum(counts.least_seconds(w, run.peak)[0] for w in work)
    return 100.0 * least / run.trace["busy_s"]
