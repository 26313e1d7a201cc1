"""Test fixture: the configuration's decode rows, read from the run."""


def read(run):
    return run.spec["engine"]["max_active"]
