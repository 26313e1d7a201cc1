"""Requests served, as a share of the requests due in the window (refusals
by Algorithm 1 and drops from the bounded queue lower it)."""


def read(run):
    return 100.0 * len(run.done()) / len(run.served) if run.served else None
