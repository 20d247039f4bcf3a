"""95th percentile of every gap between consecutive output tokens of
every request, over the gaps that end inside the window (s).  Gaps of
the drain after the close, when no new requests arrive and the batch
empties, are left out."""
import numpy as np

from common import percentile


def read(run):
    gaps = []
    for r in run.recs:
        if r.result is None or not len(r.result.itl_samples_s):
            continue
        ends = r.token_times()[1:]
        inside = (ends >= run.t0) & (ends < run.t_end)
        gaps.append(np.asarray(r.result.itl_samples_s)[inside])
    return percentile(np.concatenate(gaps) if gaps else [], 95)
