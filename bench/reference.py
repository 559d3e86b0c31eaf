"""The reference of the Llama configurations: a Llama-style block in
float32 `jax.numpy`, with no kernels, cache or batching.  A configuration
that names no ``reference`` is checked, and its work counted, by this
module (the contract is `bench.harness`'s).

It imports nothing of the program.  It reads the configuration file's
published keys (Hugging Face names) and makes its own weights from the
run's weight seed by the recipe every configuration states under
``assumed.weights``: a key split into ``layers + 3``; layer ``b`` takes
``fold_in(keys[b], 0)`` split in four, attention from the first part (split
in four: q, k, v, o), the MLP from the fourth (split in three: gate, up,
down); each matrix is a standard normal of shape (in, out) scaled by
``in**-0.5``, the embedding (vocab, hidden) one scaled by 0.02, all rounded
to the served dtype; every norm weight is one.

The block follows the published Llama description: RMSNorm, rotary
positions in the half-split (``rotate_half``) layout, grouped-query causal
attention, a SwiGLU MLP, tied input and output embeddings.  Every matmul
runs at ``highest`` precision, so the TPU does not drop to bfloat16 passes.

``lower=(bits, matmuls)`` computes the named matmuls (``"linears"``: the
seven projections of each layer; ``"lm_head"``: the output head) with both
operands quantized to ``bits``-bit symmetric integers (activations per row,
weights per column): the control that must fail the comparison.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def shape_of(model: Dict) -> Dict:
    """The sizes the reference needs, from the published keys."""
    heads = model["num_attention_heads"]
    return {"layers": model["num_hidden_layers"], "d": model["hidden_size"],
            "heads": heads, "kv_heads": model["num_key_value_heads"],
            "head_dim": model.get("head_dim",
                                  model["hidden_size"] // heads),
            "ff": model["intermediate_size"], "vocab": model["vocab_size"],
            "eps": float(model["rms_norm_eps"]),
            "theta": float(model["rope_theta"])}


def linears(shape: Dict) -> List[Tuple[int, int]]:
    """(K, N) of every projection of one token's forward pass: q, k, v, o,
    gate, up and down in every layer."""
    d, hd, ff = shape["d"], shape["head_dim"], shape["ff"]
    q, kv = shape["heads"] * hd, shape["kv_heads"] * hd
    return [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (d, ff),
            (ff, d)] * shape["layers"]


def token_ops(shape: Dict, context: int) -> int:
    """Operations of one token's attention over ``context`` keys, all
    layers: scores and the weighted sum, 2 · 2 · context · heads·head_dim."""
    return (shape["layers"] * 4 * context * shape["heads"]
            * shape["head_dim"])


def _dense(key, d_in, d_out, dtype):
    w = jax.random.normal(key, (d_in, d_out), jnp.float32)
    return (w * (1.0 / np.sqrt(d_in))).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _make(key, shape: Tuple, dtype: str):
    s = dict(shape)
    d, hd = s["d"], s["head_dim"]
    q_out, kv_out = s["heads"] * hd, s["kv_heads"] * hd
    keys = jax.random.split(key, s["layers"] + 3)

    def layer(k):
        ks = jax.random.split(jax.random.fold_in(k, 0), 4)
        ka = jax.random.split(ks[0], 4)
        km = jax.random.split(ks[3], 3)
        return {"wq": _dense(ka[0], d, q_out, dtype),
                "wk": _dense(ka[1], d, kv_out, dtype),
                "wv": _dense(ka[2], d, kv_out, dtype),
                "wo": _dense(ka[3], q_out, d, dtype),
                "w_gate": _dense(km[0], d, s["ff"], dtype),
                "w_up": _dense(km[1], d, s["ff"], dtype),
                "w_down": _dense(km[2], s["ff"], d, dtype)}

    embed = (jax.random.normal(keys[-3], (s["vocab"], d), jnp.float32)
             * 0.02).astype(dtype)
    return {"embed": embed, "layers": jax.vmap(layer)(keys[:s["layers"]])}


def make_weights(model: Dict, weight_seed: int, dtype: str) -> Dict:
    """The reference's own weights: (layers, in, out) stacks and the
    embedding, in ``dtype`` as served."""
    return _make(jax.random.PRNGKey(weight_seed),
                 tuple(sorted(shape_of(model).items())), dtype)


def _quant(x, bits: int, axis: int):
    qmax = 2.0 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -qmax, qmax), s


def _matmul(x, w, bits: Optional[int]):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if bits is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    xq, sx = _quant(x, bits, -1)
    wq, sw = _quant(w, bits, 0)
    return jnp.matmul(xq, wq, precision=HIGHEST) * sx * sw


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(W: Dict, tokens, shape: Tuple, lower: Optional[Tuple] = None):
    """(B, S, vocab) float32 logits of every position of ``tokens``."""
    s = dict(shape)
    bits, which = (lower or (None, ()))
    lin_bits = bits if "linears" in which else None
    head_bits = bits if "lm_head" in which else None
    B, S = tokens.shape
    H, Hk, hd = s["heads"], s["kv_heads"], s["head_dim"]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]                   # (q, k)
    h = W["embed"].astype(jnp.float32)[tokens]

    def block(h, p):
        x = _rms(h, s["eps"])
        q = _matmul(x, p["wq"], lin_bits).reshape(B, S, H, hd)
        k = _matmul(x, p["wk"], lin_bits).reshape(B, S, Hk, hd)
        v = _matmul(x, p["wv"], lin_bits).reshape(B, S, Hk, hd)
        q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
        k = jnp.repeat(k, H // Hk, axis=2)
        v = jnp.repeat(v, H // Hk, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
        sc = jnp.where(causal, sc / np.sqrt(hd), -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                       precision=HIGHEST).reshape(B, S, H * hd)
        h = h + _matmul(o, p["wo"], lin_bits)
        x = _rms(h, s["eps"])
        g = jax.nn.silu(_matmul(x, p["w_gate"], lin_bits))
        h = h + _matmul(g * _matmul(x, p["w_up"], lin_bits), p["w_down"],
                        lin_bits)
        return h, None

    h, _ = jax.lax.scan(block, h, W["layers"])
    return _matmul(_rms(h, s["eps"]), W["embed"].T, head_bits)


@functools.partial(jax.jit, static_argnames=("shape", "lower"))
def gaps(W: Dict, tokens, served, shape: Tuple,
         lower: Optional[Tuple] = None):
    """Per-position gap by which a token's reference logit lies below the
    reference's best, in a (B, S) array.

    ``tokens`` (B, S) is each sequence as served (prompt, then served
    tokens, then padding); ``served[b, t]`` is the token served after
    position ``t`` (−1 where nothing was served).  Without ``lower`` the gap
    is that of the served token; with it, that of the token the lowered
    control puts first at the same position, over the same tokens.
    """
    ref = logits(W, tokens, shape)
    best = jnp.max(ref, axis=-1)
    if lower is None:
        pick = jnp.maximum(served, 0)
    else:
        pick = jnp.argmax(logits(W, tokens, shape, lower), axis=-1)
    got = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
    return jnp.where(served >= 0, best - got, 0.0)
