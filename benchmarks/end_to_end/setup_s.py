"""Process start to the first timed statement."""


def compute(run):
    return run.setup_s
