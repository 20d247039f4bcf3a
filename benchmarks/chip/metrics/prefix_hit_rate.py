"""Share of prompt tokens restored from the constellation rather than
prefilled (EngineStats counters over the window)."""


def read(run):
    s = run.stats
    total = s["cached_tokens"] + s["prefilled_tokens"]
    return s["cached_tokens"] / total if total else None
