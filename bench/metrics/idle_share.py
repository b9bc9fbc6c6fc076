"""Share of the traced window in which no operation ran on the device,
in % (``bench.trace``)."""


def read(run):
    return run.trace.idle_share if run.trace is not None else None
