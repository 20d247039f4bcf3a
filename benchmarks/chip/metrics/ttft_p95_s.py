"""95th percentile of time to first token, from each request's due
time (s).  A failed request counts as missing the limit."""
from common import percentile, ttft_sample


def read(run):
    return percentile(ttft_sample(run), 95)
