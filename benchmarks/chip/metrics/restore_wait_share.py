"""Share of the window the serving loop spent blocked on a restored
prefix's decode on the fetch-ahead worker (EngineStats
``restore_wait_s`` over the window's length).  The modelled flight is
apart, in ``l2_wait_s``."""


def read(run):
    w = run.stats.get("restore_wait_s")
    return None if w is None else w / (run.t_end - run.t0)
