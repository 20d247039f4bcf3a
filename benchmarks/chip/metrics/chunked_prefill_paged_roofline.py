"""The chunked_prefill_paged kernel's least time over its device time
(%), over the traced stretch.  The least time of each call comes from
its rows' chunk starts and valid queries as the benchmark recorded them
around the step programs (a decode row is a chunk of one), at the chip's
peak compute or bandwidth, whichever bounds it, for every layer."""
import flops
import devtrace as tr

KERNEL = "chunked_prefill_paged"


def read(run):
    if run.trace is None or not run.kernel_calls:
        return None
    dev_s, n_dev = tr.device_seconds(run.trace, tr.OPS, KERNEL)
    if not n_dev or not dev_s:
        return None
    layers = run.cfg["num_hidden_layers"]
    least = sum(flops.least_seconds(*flops.paged_call(run.cfg, off, v),
                                    run.peak)
                for off, v in run.kernel_calls)
    # calls at the edges of the traced stretch may be in one record and
    # not the other: scale the least time to the kernel events traced
    least *= n_dev / (layers * len(run.kernel_calls))
    return 100.0 * least / dev_s
