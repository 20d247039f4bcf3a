"""95th percentile of the wait from the scheduler's enqueue to a slot
(s), over the requests admitted inside the window: the program's
``GenerationResult.queue_wait_s``, stamped at the first admission.
Silent where the program does not stamp it."""
from common import percentile


def read(run):
    waits = []
    for r in run.recs:
        w = getattr(r.result, "queue_wait_s", None)
        if w is not None and run.t0 <= r.handed + w < run.t_end:
            waits.append(w)
    return percentile(waits, 95)
