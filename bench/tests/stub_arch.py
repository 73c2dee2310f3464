"""A stand-in architecture plug-in whose tree is not Qwen2's, for the tests
of the plug-in seam (`test_arch_seam.py`): a decoder with no QKV bias and a
GeGLU MLP (tanh GELU on the gate), its own weight names, and its own plain
reference. It provides what `bench/archs/qwen2.py` lists, `aot_reference`
aside, and nothing is shared with that module but `bench/weights.py` and
`bench/reference.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.reference import HI, dequant, mm, rms, rope
from bench.weights import Q4_GROUP, draw_q4, draw_q8

REHEARSE_DIMS = {"L": 2, "d": 256, "f": 512, "N": 4, "K": 2, "H": 64,
                 "V": 1024}
MATS = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj",
        "down_proj")


def dims_of(cfg):
    return {"L": cfg["n_layer"], "d": cfg["d_model"], "f": cfg["d_ff"],
            "V": cfg["vocab"], "N": cfg["heads"], "K": cfg["kv_heads"],
            "H": cfg["head_dim"], "eps": cfg["eps"], "theta": cfg["theta"]}


def model_config(cfg, dims):
    from repro.config import ModelConfig
    return ModelConfig(
        name=cfg["arch"], family="transformer", num_layers=dims["L"],
        d_model=dims["d"], num_heads=dims["N"], num_kv_heads=dims["K"],
        d_ff=dims["f"], vocab_size=dims["V"], head_dim=dims["H"],
        qkv_bias=False, rope_theta=float(dims["theta"]),
        norm_eps=float(dims["eps"]), act_fn="gelu", tie_embeddings=False)


def _shapes(dims):
    d, f = dims["d"], dims["f"]
    NH, KH = dims["N"] * dims["H"], dims["K"] * dims["H"]
    return dict(zip(MATS, ((d, NH), (d, KH), (d, KH), (NH, d), (d, f),
                           (d, f), (f, d))))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _draw(items, fmts, key):
    dims = dict(items)
    L, d, V = dims["L"], dims["d"], dims["V"]
    ks = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(jnp.bfloat16)

    w = {"embed": normal((V, d), 1.0), "final_norm": normal((d,), 0.1),
         "attn_norm": normal((L, d), 0.1), "mlp_norm": normal((L, d), 0.1)}
    mats = {k: ((L,), *s) for k, s in _shapes(dims).items()}
    mats["lm_head"] = ((), d, V)
    for name, (lead, d_in, d_out) in mats.items():
        k = next(ks)
        w[name] = {f: (draw_q8 if f == "q8" else draw_q4)(
            jax.random.fold_in(k, i), lead, d_in, d_out)
            for i, f in enumerate(fmts)}
    return w


def draw(dims, fmts, key):
    keep = ("L", "d", "f", "V", "N", "K", "H")
    return _draw(tuple(sorted((k, dims[k]) for k in keep)), tuple(fmts), key)


def program_params(w, fmt, model_spec):
    from repro.quant import QTensor

    def qt(name):
        t = w[name][fmt]
        return QTensor(q=t["q"], scale=t["s"], zero=t.get("z"), fmt=fmt,
                       group=Q4_GROUP if fmt == "q4" else 0)

    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "lm_head": qt("lm_head"),
            "layers": {
                "attn": {"wq": qt("q_proj"), "wk": qt("k_proj"),
                         "wv": qt("v_proj"), "wo": qt("o_proj")},
                "norms": {"pre_attn": w["attn_norm"],
                          "pre_mlp": w["mlp_norm"]},
                "mlp": {"wg": qt("gate_proj"), "wu": qt("up_proj"),
                        "wo": qt("down_proj")}}}


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(items, fmt, act, x, w, layer):
    dims = dict(items)
    B, S, _ = x.shape
    N, K, H = dims["N"], dims["K"], dims["H"]
    m = {k: dequant(w[k][fmt], fmt, layer) for k in MATS}
    pos = jnp.arange(S, dtype=jnp.float32)
    h = rms(x, w["attn_norm"][layer], dims["eps"])
    q = rope(mm(h, m["q_proj"], act).reshape(B, S, N, H), pos, dims["theta"])
    k = rope(mm(h, m["k_proj"], act).reshape(B, S, K, H), pos, dims["theta"])
    v = mm(h, m["v_proj"], act).reshape(B, S, K, H)
    k, v = jnp.repeat(k, N // K, axis=2), jnp.repeat(v, N // K, axis=2)
    s = jnp.einsum("bqnh,bknh->bnqk", q, k, precision=HI) / H ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bnqk,bknh->bqnh", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(B, S, N * H)
    x = x + mm(o, m["o_proj"], act)
    h = rms(x, w["mlp_norm"][layer], dims["eps"])
    g = jax.nn.gelu(mm(h, m["gate_proj"], act), approximate=True)
    return x + mm(g * mm(h, m["up_proj"], act), m["down_proj"], act)


def logits_at(w, fmt, dims, seqs, *, act=None, batch=8, seq_len=0,
              positions=0):
    """Float32 logits at every served position of `seqs`, and the served
    token at each, as `bench/archs/qwen2.py`'s `logits_at` gives them."""
    items = tuple(sorted(dims.items()))
    rows = [list(p) + list(s[:-1]) for p, s in seqs]
    S = max(seq_len, *(len(r) for r in rows))
    toks = np.zeros((len(rows), S), np.int32)
    sel_b, sel_p, want = [], [], []
    for i, (r, (prompt, served)) in enumerate(zip(rows, seqs)):
        toks[i, :len(r)] = r
        sel_b += [i] * len(served)
        sel_p += [len(prompt) - 1 + j for j in range(len(served))]
        want += list(served)
    x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
    for layer in range(dims["L"]):
        x = _layer(items, fmt, act, x, w, layer)
    x_sel = x[jnp.asarray(sel_b), jnp.asarray(sel_p)]
    return (reference.head_logits(dims["eps"], fmt, act, x_sel,
                                  w["final_norm"], w["lm_head"][fmt]),
            np.asarray(want, np.int64))


def body_flops(dims):
    return sum(2 * k * n for k, n in _shapes(dims).values()) * dims["L"]


def head_flops(dims):
    return 2 * dims["d"] * dims["V"]


def mixer_flops(dims, keys):
    return 4 * dims["L"] * dims["N"] * dims["H"] * keys
