"""Helpers shared by the end-to-end readers."""
import numpy as np


def ttft_sample(run) -> np.ndarray:
    """Time to first token of every request of the window, from its due
    time; a failed request is +inf (it missed every limit)."""
    return np.asarray([r.token_times()[0] - r.due if r.result is not None
                       else np.inf for r in run.recs])


def percentile(xs, q):
    xs = np.asarray(xs, np.float64)
    if not len(xs):
        return None
    v = float(np.percentile(xs, q))
    return v if np.isfinite(v) else None
