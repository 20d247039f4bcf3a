"""Output tokens emitted inside the window, over the window (tokens/s).
Tokens of requests still running when the window closes count up to
its end."""


def read(run):
    n = sum(int(((t >= run.t0) & (t < run.t_end)).sum())
            for t in (r.token_times() for r in run.recs
                      if r.result is not None))
    return n / (run.t_end - run.t0)
