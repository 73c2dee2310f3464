"""Plain reference of the served model: a Qwen2 decoder in float32.

It follows the Qwen2 description (arXiv:2407.10671; Hugging Face
`Qwen2ForCausalLM`): RMSNorm before attention and before the MLP, QKV
projections with bias, rotary position embedding on the two halves of each
head (theta from the config), causal grouped-query attention (query head n
reads KV head n // (N/K)), a SwiGLU MLP, a final RMSNorm and an untied LM
head. One departure in form only: a norm weight is stored as its offset
from 1, as the benchmark's weights hold it (`bench/weights.py`).

It imports nothing of the program and reads only the benchmark's plain
weight dict. Every matrix is dequantized to float32 from the served codes
and every product runs at `Precision.HIGHEST`. Layers run one at a time
(one compiled program, indexed by layer) and attention one sequence at a
time, so the whole pass fits beside the served weights on one chip.

`act="int8"` (or `"fp8"`) is the control: the same pass with the input of
every matrix product rounded per token to int8 (symmetric, scaled by the
row's absolute maximum) or to float8 e4m3 (scaled so the row's absolute
maximum is 448), a step below the bf16 activations the configuration
states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Q4_GROUP

HI = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def dequant(t: dict, fmt: str, layer=None) -> jax.Array:
    """Float32 matrix (d_in, d_out) from one served format's codes."""
    def pick(a):
        return a if layer is None else a[layer]
    if fmt == "q8":
        return pick(t["q"]).astype(jnp.float32) * pick(t["s"])
    packed = pick(t["q"])
    lo = (packed & 0x0F).astype(jnp.float32)
    hi = (packed >> 4).astype(jnp.float32)
    half, d_out = packed.shape
    codes = jnp.stack([lo, hi], axis=1).reshape(half * 2, d_out)
    g = codes.reshape(-1, Q4_GROUP, d_out)
    w = g * pick(t["s"])[:, None, :] + pick(t["z"])[:, None, :]
    return w.reshape(half * 2, d_out)


def _act_round(x, act):
    if act is None:
        return x
    top = {"int8": 127.0, "fp8": 448.0}[act]
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / top
    if act == "int8":
        return jnp.round(x / s) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, act):
    return jnp.dot(_act_round(x, act), w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    H = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, H, 2, dtype=jnp.float32) / H)
    ang = pos[:, None] * inv                              # (S, H/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :H // 2], x[..., H // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(dims_items, fmt, act, x, w, layer):
    dims = dict(dims_items)
    B, S, d = x.shape
    N, K, H = dims["N"], dims["K"], dims["H"]
    mats = {k: dequant(w[k][fmt], fmt, layer) for k in LAYER_KEYS}
    pos = jnp.arange(S, dtype=jnp.float32)
    h = _rms(x, w["ln1"][layer], dims["eps"])
    q = (_mm(h, mats["wq"], act) + w["bq"][layer]).reshape(B, S, N, H)
    k = (_mm(h, mats["wk"], act) + w["bk"][layer]).reshape(B, S, K, H)
    v = (_mm(h, mats["wv"], act) + w["bv"][layer]).reshape(B, S, K, H)
    q, k = _rope(q, pos, dims["theta"]), _rope(k, pos, dims["theta"])
    k, v = jnp.repeat(k, N // K, axis=2), jnp.repeat(v, N // K, axis=2)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def attend(qkv):
        qi, ki, vi = qkv                                  # (S, N, H)
        s = jnp.einsum("qnh,knh->nqk", qi, ki, precision=HI) / H ** 0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,knh->qnh", p, vi, precision=HI)

    o = jax.lax.map(attend, (q, k, v)).reshape(B, S, N * H)
    x = x + _mm(o, mats["wo"], act)
    h = _rms(x, w["ln2"][layer], dims["eps"])
    m = jax.nn.silu(_mm(h, mats["wg"], act)) * _mm(h, mats["wu"], act)
    return x + _mm(m, mats["wd"], act)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _head(dims_items, fmt, act, cols, x_sel, final_norm, lm_head, start):
    """Logits of vocabulary columns [start, start + cols)."""
    h = _rms(x_sel, final_norm, dict(dims_items)["eps"])
    part = {k: jax.lax.dynamic_slice_in_dim(a, start, cols, axis=-1)
            for k, a in lm_head.items()}
    return _mm(h, dequant(part, fmt), act)


def head_logits(dims, fmt, act, x_sel, final_norm, lm_head,
                max_cols: int = 40000) -> np.ndarray:
    """The LM head a slice of the vocabulary at a time, so that only one
    slice is ever dequantized to float32."""
    V = dims["V"]
    parts = next(p for p in range(1, V + 1) if V % p == 0 and V // p <= max_cols)
    cols = V // parts
    items = tuple(sorted(dims.items()))
    return np.concatenate([np.asarray(_head(items, fmt, act, cols, x_sel,
                                            final_norm, lm_head, i * cols))
                           for i in range(parts)], axis=-1)


def layer_weights(w: dict, fmt: str) -> dict:
    """The stacked per-layer arrays of one format, as `_layer` reads them."""
    out = {k: w[k] for k in ("ln1", "ln2", "bq", "bk", "bv")}
    out.update({k: {fmt: w[k][fmt]} for k in LAYER_KEYS})
    return out


def logits_at(w: dict, fmt: str, dims: dict, seqs, *, act=None,
              batch: int = 8, seq_len: int = 0, positions: int = 0):
    """Float32 logits at every served position of `seqs`.

    `seqs` is a list of (prompt, served tokens). The served token j of a
    request is predicted at position len(prompt) - 1 + j of the sequence
    prompt + served[:-1]. Returns (n_positions, V) logits and the served
    token at each position, in request order. Sequences are padded to at
    least `seq_len` and the positions of a batch to at least `positions`,
    each rounded up, so that a cell's runs share one compiled pass."""
    items = tuple(sorted((k, v) for k, v in dims.items()))
    lw = layer_weights(w, fmt)
    rows = [list(prompt) + list(served[:-1]) for prompt, served in seqs]
    S = -(-max(seq_len, *(len(r) for r in rows)) // 128) * 128
    out, want = [], []
    for lo in range(0, len(rows), batch):
        chunk = rows[lo:lo + batch]
        toks = np.zeros((batch, S), np.int32)            # right-padded: causal
        sel_b, sel_p, targets = [], [], []
        for i, r in enumerate(chunk):
            toks[i, :len(r)] = r
            prompt, served = seqs[lo + i]
            for j, t in enumerate(served):
                sel_b.append(i)
                sel_p.append(len(prompt) - 1 + j)
                targets.append(t)
        x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
        for layer in range(dims["L"]):
            x = _layer(items, fmt, act, x, lw, layer)
        n = len(sel_b)
        pad = -max(n, positions) % 256 + max(positions - n, 0)
        x_sel = x[jnp.asarray(sel_b + [0] * pad), jnp.asarray(sel_p + [0] * pad)]
        del x
        out.append(head_logits(dims, fmt, act, x_sel, w["final_norm"],
                               w["lm_head"][fmt])[:n])
        want.extend(targets)
    return np.concatenate(out), np.asarray(want, np.int64)


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the reference's best."""
    picked = ref_logits[np.arange(len(tokens)), tokens]
    return ref_logits.max(axis=-1) - picked
