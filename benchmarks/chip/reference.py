"""The plain reference: weights from the seed and a float32 forward pass.

Nothing here imports the system under test.  ``init_params`` makes the
weights of a dense GQA decoder (the InternLM2 / Yi / Llama block: RMSNorm,
rotary attention with grouped K/V heads, SwiGLU MLP, untied output head)
from a seed, in the layout the serving program takes them, in one jitted
call on the device.  ``logits_at`` is the architecture's forward pass in
straightforward ``jax.numpy`` at float32 with every matrix product at
``HIGHEST`` precision, evaluated one sequence at a time and layer by layer
so that a 4096-token sequence fits beside nothing else.

Rotary embeddings rotate adjacent pairs of each head's dimensions, as the
original Llama and InternLM code does; a checkpoint in the half-split
layout is the same model with the query and key columns permuted.

``fp8=True`` gives the control: every weight matrix product computed from
operands rounded to float8 e4m3 (per-tensor scale for weights, per-row
scale for activations), the precision below the configuration's bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def dims(cfg: dict) -> dict:
    """The sizes the forward pass needs, from a configuration file."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, h=h, hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                ff=cfg["intermediate_size"], layers=cfg["num_hidden_layers"],
                vocab=cfg["vocab_size"], theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def param_shapes(cfg: dict) -> dict:
    """Shape and dtype of every weight, in the serving program's layout."""
    z = dims(cfg)
    d, h, hkv, hd, ff, n, v = (z["d"], z["h"], z["hkv"], z["hd"], z["ff"],
                               z["layers"], z["vocab"])
    bf, f32 = jnp.bfloat16, jnp.float32

    def s(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt)

    return {
        "embed": {"tok": s((v, d)), "unembed": s((d, v))},
        "blocks": {
            "norm1": {"scale": s((n, d), f32)},
            "norm2": {"scale": s((n, d), f32)},
            "attn": {"wq": s((n, d, h * hd)), "wk": s((n, d, hkv * hd)),
                     "wv": s((n, d, hkv * hd)), "wo": s((n, h * hd, d))},
            "mlp": {"wi_gate": s((n, d, ff)), "wi_up": s((n, d, ff)),
                    "wo": s((n, ff, d))},
        },
        "final_norm": {"scale": s((d,), f32)},
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    words = np.random.SeedSequence(int(seed)).generate_state(1)
    return jax.random.PRNGKey(int(words[0]))


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Random weights: fan-in scaled truncated normals, unit norm scales."""
    shapes = param_shapes(cfg)
    leaves, tree = jax.tree.flatten(shapes)
    keys = jax.random.split(key, len(leaves))

    def make(k, s):
        if s.dtype == jnp.float32:            # norm scales
            return jnp.ones(s.shape, s.dtype)
        fan_in = s.shape[-1] if s.shape[0] == dims(cfg)["vocab"] \
            else s.shape[-2]
        w = jax.random.truncated_normal(k, -2.0, 2.0, s.shape, jnp.float32)
        return (w * fan_in ** -0.5).astype(s.dtype)

    return jax.tree.unflatten(tree, [make(k, s) for k, s in
                                     zip(keys, leaves)])


def make_params(cfg: dict, seed: int) -> dict:
    """``init_params`` in one jitted call on the default device."""
    return jax.jit(partial(init_params, cfg))(seed_key(seed))


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale over ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, fp8: bool):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate adjacent pairs of the last axis; x [S, H, D], pos [S]."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv   # [S, 1, D/2]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _attend(q, k, v, block: int):
    """Causal GQA attention, queries in blocks; q [S,H,D], k/v [S,Hkv,D]."""
    s, h, hd = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(s)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * hd ** -0.5
        qpos = i * block + jnp.arange(block)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(s // block))
    return out.reshape(s, h, hd)


@partial(jax.jit, static_argnames=("cfg_items", "rows", "fp8"))
def _logits_rows(params, tokens, first, *, cfg_items, rows, fp8):
    cfg = dict(cfg_items)
    z = dims(cfg)
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = params["embed"]["tok"][tokens].astype(jnp.float32)

    def layer(x, p):
        hn = _rms(x, p["norm1"]["scale"], z["eps"])
        a = p["attn"]
        q = _mm(hn, a["wq"], fp8).reshape(s, z["h"], z["hd"])
        k = _mm(hn, a["wk"], fp8).reshape(s, z["hkv"], z["hd"])
        v = _mm(hn, a["wv"], fp8).reshape(s, z["hkv"], z["hd"])
        q, k = _rope(q, pos, z["theta"]), _rope(k, pos, z["theta"])
        o = _attend(q, k, v, min(512, s)).reshape(s, z["h"] * z["hd"])
        x = x + _mm(o, a["wo"], fp8)
        hn = _rms(x, p["norm2"]["scale"], z["eps"])
        m = p["mlp"]
        g = jax.nn.silu(_mm(hn, m["wi_gate"], fp8)) * _mm(hn, m["wi_up"], fp8)
        return x + _mm(g, m["wo"], fp8), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = jax.lax.dynamic_slice_in_dim(x, first, rows)
    x = _rms(x, params["final_norm"]["scale"], z["eps"])
    return _mm(x, params["embed"]["unembed"], fp8)


def logits_at(params, cfg: dict, tokens, first: int, count: int, *,
              rows: int, pad_to: int, fp8: bool = False) -> np.ndarray:
    """Float32 logits ``[count, vocab]`` at positions ``first ...
    first + count - 1`` of ``tokens`` (each predicting the token after
    it).  The sequence is zero-padded to ``pad_to`` (a multiple of 512)
    and the head runs on a fixed ``rows`` positions, so one compilation
    serves every sequence of a run; padding lies after every position
    read, and the causal mask keeps it out."""
    if count > rows or first + count > pad_to or len(tokens) > pad_to:
        raise ValueError("sequence does not fit the reference's shape")
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    start = min(first, pad_to - rows)
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    out = _logits_rows(params, jnp.asarray(toks), jnp.int32(start),
                       cfg_items=items, rows=rows, fp8=fp8)
    return np.asarray(out[first - start:first - start + count], np.float32)


def greedy_gaps(ref_rows: np.ndarray, tokens) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best, in standard deviations of that row's logits (0 for the argmax)."""
    tokens = np.asarray(tokens)
    best = ref_rows.max(-1)
    mine = ref_rows[np.arange(len(tokens)), tokens]
    return (best - mine) / ref_rows.std(-1)
