"""Process start to the window's opening: imports, weights, the cluster,
the documents stored, every shape warmed (and compiled, on a first run)."""


def read(run):
    return run.setup_s
