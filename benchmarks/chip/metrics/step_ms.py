"""Device time of one step program, averaged over the decode, mixed and
chunk-wave programs that ran in the traced stretch (ms)."""
import devtrace as tr

STEPS = ("_paged_step", "_mixed_step", "prefill_chunk_paged")


def read(run):
    if run.trace is None:
        return None
    secs = n = 0
    for m in STEPS:
        s, k = tr.device_seconds(run.trace, tr.MODULES, m)
        secs, n = secs + s, n + k
    return 1e3 * secs / n if n else None
