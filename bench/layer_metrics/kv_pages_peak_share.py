"""Peak pages in use as a share of the pool (``PagedEngine.counters()``)."""


def read(run):
    return 100.0 * run.counters["peak_pages"] / run.spec["engine"]["num_pages"]
