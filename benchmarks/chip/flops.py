"""Operations and bytes the work needs, from the configuration's sizes.

``token_flops`` counts what a dense GQA decoder must compute for one
token at a position: every weight matrix product (2 operations a
multiply-add), attention's scores and weighted sum over the keys up to it,
and the output head only where a logit is needed.  Norms, rotary
embeddings and softmax are left out: they are a rounding error beside the
products.  Recomputed work (the write-back's forward passes) is never
counted: it is not work the served tokens need.

``paged_call`` gives the operations and least bytes of one call of the
``chunked_prefill_paged`` kernel per layer: each valid query attends over
the keys up to its position; the keys and values of those positions are
read once, the queries read and the outputs written once.
"""
from __future__ import annotations

import numpy as np

from reference import dims


def matmul_params(cfg: dict) -> tuple[int, int]:
    """Weights in the layers' matrix products, and in the output head."""
    z = dims(cfg)
    d, q, kv = z["d"], z["h"] * z["hd"], z["hkv"] * z["hd"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * z["ff"]
    return z["layers"] * per_layer, d * z["vocab"]


def token_flops(cfg: dict, position: np.ndarray, head: np.ndarray
                ) -> float:
    """Operations for tokens at ``position`` (0-based; each attends over
    ``position + 1`` keys), with the output head where ``head`` is true."""
    z = dims(cfg)
    body, out = matmul_params(cfg)
    position = np.asarray(position, np.float64)
    attn = 4.0 * z["layers"] * z["h"] * z["hd"] * (position + 1)
    return float(np.sum(2.0 * body + attn + 2.0 * out * np.asarray(head)))


def paged_call(cfg: dict, offsets, valid, itemsize: int = 2
               ) -> tuple[float, float]:
    """``(operations, bytes)`` of one layer's ``chunked_prefill_paged``
    call over rows with chunk starts ``offsets`` and ``valid`` queries each
    (a decode row is a chunk of one at its position)."""
    z = dims(cfg)
    off = np.asarray(offsets, np.float64)
    v = np.asarray(valid, np.float64)
    keys = v * off + v * (v + 1) / 2          # sum over queries of keys seen
    flops = 4.0 * z["h"] * z["hd"] * keys.sum()
    kv_bytes = 2.0 * (off + v) * z["hkv"] * z["hd"] * itemsize
    qo_bytes = 2.0 * v * z["h"] * z["hd"] * itemsize
    return float(flops), float(kv_bytes.sum() + qo_bytes.sum())


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The larger of compute time at peak and transfer time at peak."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])
