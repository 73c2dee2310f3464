"""Qwen2: what the benchmark knows of this architecture, as one plug-in.

A configuration file names its plug-in by `"bench_arch"`; `bench/harness.py`
loads `bench/archs/<name>.py` by its path and reads nothing of the
architecture but what the module gives:

  REHEARSE_DIMS            the reduced sizes of a `--rehearse` run
  dims_of(cfg)             the sizes the weights and the reference need, from
                           the config file; shared code reads only "V"
  model_config(cfg, dims)  the program's `ModelConfig`
  draw(dims, fmts, key)    the seeded weight draw, in the served formats
  program_params(w, fmt, spec)
                           one variant of the draw as the program's tree
  logits_at(w, fmt, dims, seqs, ...)
                           the plain float32 reference's logits
  body_flops, head_flops, mixer_flops
                           the operations of one position that `mfu` counts
  aot_reference(w, fmt, dims, sharding)
                           the reference's programs, lowered for `bench/aot.py`

The weights: the bf16 embedding, norms and QKV biases, and for each quantized
matrix both served formats (`bench/weights.py`). The reference follows the
Qwen2 description (arXiv:2407.10671; Hugging Face `Qwen2ForCausalLM`):
RMSNorm before attention and before the MLP, QKV projections with bias,
rotary position embedding on the two halves of each head (theta from the
config), causal grouped-query attention (query head n reads KV head
n // (N/K)), a SwiGLU MLP, a final RMSNorm and an untied LM head. One
departure in form only: a norm weight is stored as its offset from 1, as the
benchmark's weights hold it. Layers run one at a time (one compiled program,
indexed by layer) and attention one sequence at a time, so the whole pass
fits beside the served weights on one chip.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.reference import HI, dequant, mm, rms, rope
from bench.weights import Q4_GROUP, draw_q4, draw_q8

REHEARSE_DIMS = {"L": 2, "d": 256, "f": 512, "N": 4, "K": 2, "H": 64,
                 "V": 1024}
LAYER_KEYS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
# the sizes the weight draw depends on (the rest only the reference reads)
_DRAW_KEYS = ("L", "d", "f", "V", "N", "K", "H")


def matrices(dims):
    """name -> (stacked over layers?, d_in, d_out) for each quantized matrix."""
    d, f, V = dims["d"], dims["f"], dims["V"]
    NH, KH = dims["N"] * dims["H"], dims["K"] * dims["H"]
    return {"wq": (True, d, NH), "wk": (True, d, KH), "wv": (True, d, KH),
            "wo": (True, NH, d), "wg": (True, d, f), "wu": (True, d, f),
            "wd": (True, f, d), "lm_head": (False, d, V)}


def dims_of(cfg: dict) -> dict:
    """The sizes the weights and the reference need, from a config file."""
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "N": cfg["num_attention_heads"], "K": cfg["num_key_value_heads"],
            "H": cfg["assumed"]["head_dim"], "eps": cfg["rms_norm_eps"],
            "theta": cfg["rope_theta"]}


def model_config(cfg: dict, dims: dict):
    """The program's `ModelConfig`: the registered architecture at `dims`."""
    from repro.common.registry import get_arch
    return dataclasses.replace(
        get_arch(cfg["arch"]), num_layers=dims["L"], d_model=dims["d"],
        d_ff=dims["f"], vocab_size=dims["V"], num_heads=dims["N"],
        num_kv_heads=dims["K"], head_dim=dims["H"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        qkv_bias=bool(cfg["assumed"]["qkv_bias"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), act_fn="silu")


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make(dims_items, fmts, key):
    dims = dict(dims_items)
    L, d, V = dims["L"], dims["d"], dims["V"]
    NH, KH = dims["N"] * dims["H"], dims["K"] * dims["H"]
    ks = iter(jax.random.split(key, 64))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(jnp.bfloat16)

    w = {"embed": normal((V, d), 1.0),
         "final_norm": normal((d,), 0.1),
         "ln1": normal((L, d), 0.1), "ln2": normal((L, d), 0.1),
         "bq": normal((L, NH), 0.1), "bk": normal((L, KH), 0.1),
         "bv": normal((L, KH), 0.1)}
    for name, (stacked, d_in, d_out) in matrices(dims).items():
        lead = (L,) if stacked else ()
        w[name] = {}
        for fmt in fmts:
            draw = draw_q8 if fmt == "q8" else draw_q4
            w[name][fmt] = draw(next(ks), lead, d_in, d_out)
    return w


def draw(dims: dict, fmts: tuple, key) -> dict:
    """All weights for `dims`, both formats of every quantized matrix, drawn
    from `key` on the default device in one program."""
    items = tuple(sorted((k, v) for k, v in dims.items() if k in _DRAW_KEYS))
    return _make(items, tuple(fmts), key)


def program_params(w: dict, fmt: str, model_spec):
    """One variant of `w` as the program's parameter tree (QTensor leaves),
    checked against the structure the program's quantized spec asks for."""
    from repro.quant import QTensor, quant_spec
    from repro.sharding.param import ParamDef

    def qt(name):
        t = w[name][fmt]
        return QTensor(q=t["q"], scale=t["s"], zero=t.get("z"), fmt=fmt,
                       group=Q4_GROUP if fmt == "q4" else 0)

    # the program stores a norm weight as its offset from 1, as `w` does
    params = {
        "embed": w["embed"],
        "final_norm": w["final_norm"],
        "lm_head": qt("lm_head"),
        "layers": {
            "attn": {"wq": qt("wq"), "wk": qt("wk"), "wv": qt("wv"),
                     "wo": qt("wo"), "bq": w["bq"], "bk": w["bk"],
                     "bv": w["bv"]},
            "norms": {"pre_attn": w["ln1"], "pre_mlp": w["ln2"]},
            "mlp": {"wg": qt("wg"), "wu": qt("wu"), "wo": qt("wd")},
        },
    }
    want = jax.tree.structure(quant_spec(model_spec, fmt),
                              is_leaf=lambda x: isinstance(x, ParamDef))
    got = jax.tree.structure(params)
    if want != got:
        raise ValueError(f"the program's {fmt} parameter tree changed shape:\n"
                         f"want {want}\ngot  {got}")
    return params


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(dims_items, fmt, act, x, w, layer):
    dims = dict(dims_items)
    B, S, d = x.shape
    N, K, H = dims["N"], dims["K"], dims["H"]
    mats = {k: dequant(w[k][fmt], fmt, layer) for k in LAYER_KEYS}
    pos = jnp.arange(S, dtype=jnp.float32)
    h = rms(x, w["ln1"][layer], dims["eps"])
    q = (mm(h, mats["wq"], act) + w["bq"][layer]).reshape(B, S, N, H)
    k = (mm(h, mats["wk"], act) + w["bk"][layer]).reshape(B, S, K, H)
    v = (mm(h, mats["wv"], act) + w["bv"][layer]).reshape(B, S, K, H)
    q, k = rope(q, pos, dims["theta"]), rope(k, pos, dims["theta"])
    k, v = jnp.repeat(k, N // K, axis=2), jnp.repeat(v, N // K, axis=2)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def attend(qkv):
        qi, ki, vi = qkv                                  # (S, N, H)
        s = jnp.einsum("qnh,knh->nqk", qi, ki, precision=HI) / H ** 0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,knh->qnh", p, vi, precision=HI)

    o = jax.lax.map(attend, (q, k, v)).reshape(B, S, N * H)
    x = x + mm(o, mats["wo"], act)
    h = rms(x, w["ln2"][layer], dims["eps"])
    m = jax.nn.silu(mm(h, mats["wg"], act)) * mm(h, mats["wu"], act)
    return x + mm(m, mats["wd"], act)


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
    each rounded up, so that a cell's runs share one compiled pass.
    `act` rounds every product's input (`reference.mm`): the control."""
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
        out.append(reference.head_logits(dims["eps"], fmt, act, x_sel,
                                         w["final_norm"],
                                         w["lm_head"][fmt])[:n])
        want.extend(targets)
    return np.concatenate(out), np.asarray(want, np.int64)


def matmul_shapes(dims: dict):
    """(K, N) of every quantized matrix product of one token's forward pass,
    in the order the model runs them: per layer q, k, v, o, gate, up, down,
    then the LM head."""
    d, f, V = dims["d"], dims["f"], dims["V"]
    NH, KH = dims["N"] * dims["H"], dims["K"] * dims["H"]
    layer = [(d, NH), (d, KH), (d, KH), (NH, d), (d, f), (d, f), (f, d)]
    return layer * dims["L"] + [(d, V)]


def body_flops(dims: dict) -> float:
    """Weight-product operations of one position, LM head left out (a
    prompt needs the head at its last position only)."""
    return sum(2 * k * n for k, n in matmul_shapes(dims)[:-1])


def head_flops(dims: dict) -> float:
    return 2 * dims["d"] * dims["V"]


def mixer_flops(dims: dict, keys: float) -> float:
    """QK and PV products of one query position over `keys` positions."""
    return 4 * dims["L"] * dims["N"] * dims["H"] * keys


def aot_reference(w, fmt: str, dims: dict, sharding):
    """(name, lowered program) of the reference's layer and LM head at the
    largest shapes a run hands them (8 sequences of 1024, 1024 positions),
    for `bench/aot.py`. `w` holds shapes placed on `sharding`."""
    i32 = jnp.int32
    items = tuple(sorted(dims.items()))
    x = jax.ShapeDtypeStruct((8, 1024, dims["d"]), jnp.float32,
                             sharding=sharding)
    yield "reference layer (8, 1024)", _layer.lower(
        items, fmt, None, x, layer_weights(w, fmt),
        jax.ShapeDtypeStruct((), i32, sharding=sharding))
    xs = jax.ShapeDtypeStruct((1024, dims["d"]), jnp.float32,
                              sharding=sharding)
    cols = reference.head_cols(dims["V"])
    yield (f"reference head (1024 positions, {cols} columns)",
           reference.head_slice.lower(
               dims["eps"], fmt, None, cols, xs, w["final_norm"],
               w["lm_head"][fmt], jax.ShapeDtypeStruct((), i32,
                                                       sharding=sharding)))
