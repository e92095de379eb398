"""Device operations per request (one raw window) in the traced window:
what the executor dispatches for one classification."""


def read(run):
    if run.trace is None or not run.record.attempted or not run.trace.ops:
        return None
    return run.trace.ops / run.record.attempted
