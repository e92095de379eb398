"""setup_s: seconds from the start of building the system (weights,
traffic, the program's compile and lowering) to the end of its warm-up."""


def read(run):
    return run.setup_s
