"""Process start to the first timed arrival: imports, weights, build,
warm-up and every compile."""


def read(run):
    return run.setup_s
