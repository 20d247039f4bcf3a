"""Running sequences per decode step: decoded tokens over steps
launched (EngineStats counters over the window)."""


def read(run):
    s = run.stats
    return s["decoded_tokens"] / s["decode_steps"] if s["decode_steps"] \
        else None
