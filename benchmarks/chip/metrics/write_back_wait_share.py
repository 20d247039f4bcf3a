"""Share of the window the serving loop spent blocked on the previous
request's write-back before a lookup (EngineStats ``write_back_wait_s``
over the window's length): the wait an admission pays for Set KVC."""


def read(run):
    w = run.stats.get("write_back_wait_s")
    return None if w is None else w / (run.t_end - run.t0)
