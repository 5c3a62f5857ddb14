"""Set-up: process start to the first timed request, compiles included."""


def read(run):
    return run.setup_s
