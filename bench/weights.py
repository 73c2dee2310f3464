"""Seeded random weights for a configuration, made by the benchmark itself.

`make(dims, seed)` draws every array on the device in one jitted call, in the
types they are served in: the bf16 embedding, norms and QKV biases, and for
each quantized matrix both served formats,

  q8: int8 codes (d_in, d_out), one f32 scale per output column;
  q4: 4-bit codes packed two per uint8 along d_in (row 2i in the low nibble,
      row 2i+1 in the high one), f32 scale and offset per group of 128 rows
      and output column.

The Q4 tree is drawn on its own, not quantized from the Q8 tree: each cell's
reference dequantizes the codes of the variant it serves. Scales are sized so
that every dequantized matrix has a standard deviation near 1/sqrt(d_in).

The result is a plain dict of arrays (see `shapes`). `program_params` wraps
one variant of it in the program's parameter tree; `bench/reference.py` reads
the plain dict and nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q4_GROUP = 128
# standard deviation of a code drawn uniformly from int8 (-128..127) and
# from the 16 levels of a nibble
_Q8_STD = 73.9
_Q4_STD = 4.61


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


def _scale(key, shape, std_code, d_in):
    u = jax.random.uniform(key, shape, jnp.float32, 0.75, 1.25)
    return u / (std_code * d_in ** 0.5)


def _q8(key, lead, d_in, d_out):
    kq, ks = jax.random.split(key)
    q = jax.lax.bitcast_convert_type(
        jax.random.bits(kq, (*lead, d_in, d_out), jnp.uint8), jnp.int8)
    return {"q": q, "s": _scale(ks, (*lead, 1, d_out), _Q8_STD, d_in)}


def _q4(key, lead, d_in, d_out):
    kq, ks, kz = jax.random.split(key, 3)
    q = jax.random.bits(kq, (*lead, d_in // 2, d_out), jnp.uint8)
    s = _scale(ks, (*lead, d_in // Q4_GROUP, d_out), _Q4_STD, d_in)
    # offset centres the codes (mean 7.5) near 0, give or take a tenth
    z = -s * (7.5 + jax.random.uniform(kz, s.shape, jnp.float32, -0.75, 0.75))
    return {"q": q, "s": s, "z": z}


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
            draw = _q8 if fmt == "q8" else _q4
            w[name][fmt] = draw(next(ks), lead, d_in, d_out)
    return w


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, wider than 32 bits too,
    for XLA's RngBitGenerator ("rbg"): the same seed gives the same weights
    on a given backend. Its draw time on the chip is in PERF.md."""
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def make(dims: dict, seed: int, fmts=("q8", "q4")) -> dict:
    """All weights for `dims`, both formats of every quantized matrix, drawn
    from `seed` on the default device in one program."""
    key = seed_key(seed)
    items = tuple(sorted((k, v) for k, v in dims.items()
                         if k in ("L", "d", "f", "V", "N", "K", "H")))
    return _make(items, tuple(fmts), key)


def drop_format(w: dict, fmt: str) -> dict:
    """`w` without format `fmt` (frees it once no other reference holds it)."""
    return {k: ({f: t for f, t in v.items() if f != fmt}
                if isinstance(v, dict) else v) for k, v in w.items()}


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
