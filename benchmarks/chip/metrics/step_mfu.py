"""Model operations of the tokens served in the window over what the
chip's peak could do in it (%).  A decode token counts its whole forward
pass with the output head; a prompt's uncached tokens count their
forward pass, the head only on the last, at the time its first token
came out.  Restored tokens and the write-back's recompute do not count."""
import numpy as np

import flops


def read(run):
    total = 0.0
    for r in run.recs:
        if r.result is None:
            continue
        times = r.token_times()
        n, c = r.result.prompt_tokens, r.result.cached_tokens
        if run.t0 <= times[0] < run.t_end and n > c:
            pos = np.arange(c, n)
            total += flops.token_flops(run.cfg, pos, pos == n - 1)
        # token k > 0 is decoded from the token before it at n + k - 1
        inside = np.nonzero((times >= run.t0) & (times < run.t_end))[0]
        inside = inside[inside > 0]
        total += flops.token_flops(run.cfg, n + inside - 1,
                                   np.ones(len(inside), bool))
    return 100.0 * total / ((run.t_end - run.t0) * run.peak["bf16_flops"])
