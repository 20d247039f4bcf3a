"""Share of the traced stretch in which the device ran no operation."""


def read(run):
    if run.busy_s is None:
        return None
    return 1.0 - run.busy_s / run.trace_s
