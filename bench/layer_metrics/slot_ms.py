"""Host wall time per control slot that dispatched device work, over the
window: from such a slot's start to the next slot's start. (Slots with no
work return at once and are left out, so a light load does not read as a
fast loop.)"""


def read(run):
    end = run.t0 + run.seconds
    spans = [run.starts.get(t + 1, run.ends[t]) - run.starts[t]
             for t in run.dispatching if run.t0 <= run.starts[t] < end]
    return 1e3 * sum(spans) / len(spans) if spans else None
