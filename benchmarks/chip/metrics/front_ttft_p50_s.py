"""Median time to first token, from each request's due time (s), as the
front door sees it.  Over a dozen requests a window the median swings
with which two requests sit in the middle, so it stands beside
``ttft_p95_s`` as a layer's reading, not as an end-to-end metric."""
from common import percentile, ttft_sample


def read(run):
    return percentile(ttft_sample(run), 50)
