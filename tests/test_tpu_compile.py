"""The main path's Pallas kernels compile for a TPU v5e at the paper model's
widths (Qwen2-7B: d 3584, d_ff 18944, vocab 152064, 28/4 GQA heads of 128).

Nothing runs: each test lowers one kernel against a v5e that is described,
not attached, and compiles it with the TPU compiler, which refuses what
interpret mode accepts (block shapes off the (8, 128) tiling, casts the
chip has no lowering for, too much VMEM). The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
under pytest-xdist every worker imports this file.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.flash_attention import flash_attention_bnh
from repro.kernels.paged_attention.paged_attention import paged_attention_bkgh
from repro.kernels.quant_matmul.quant_matmul import q4_matmul, q8_matmul
from repro.kernels.topk_sim.topk_sim import sim_scores

LAYERS, D, FF, VOCAB = 28, 3584, 18944, 152064
N_HEADS, K_HEADS, HEAD = 28, 4, 128
BLOCK, POOL_BLOCKS, CHAIN = 16, 154, 16       # the smoke run's paged pool
DECODE_ROWS = 8                               # quant_matmul pads decode to 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("splits", [1, 2])
def test_paged_attention_compiles(one_chip, kv_dtype, splits):
    G = N_HEADS // K_HEADS
    pool = (POOL_BLOCKS, BLOCK, K_HEADS, HEAD)
    shapes = [((DECODE_ROWS, K_HEADS, G, HEAD), jnp.bfloat16),
              (pool, jnp.bfloat16 if kv_dtype == "bf16" else jnp.int8),
              (pool, jnp.bfloat16 if kv_dtype == "bf16" else jnp.int8),
              ((DECODE_ROWS, CHAIN), jnp.int32), ((DECODE_ROWS,), jnp.int32)]
    if kv_dtype == "int8":
        shapes += [(pool[:3], jnp.float32)] * 2

    def fn(q, kp, vp, bt, lens, *scales):
        ks, vs = scales or (None, None)
        return paged_attention_bkgh(q, kp, vp, bt, lens, k_scale=ks,
                                    v_scale=vs, num_splits=splits)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("fmt", ["q8", "q4"])
@pytest.mark.parametrize("n_out", [FF, K_HEADS * HEAD, VOCAB])
def test_quant_matmul_compiles(one_chip, fmt, n_out):
    x = ((DECODE_ROWS, D), jnp.bfloat16)
    if fmt == "q8":         # a lone matrix is a stack of one
        _compile(lambda x, w, s: q8_matmul(x, w[None], s[None], 0), one_chip,
                 x, ((D, n_out), jnp.int8), ((1, n_out), jnp.float32))
    else:
        groups = ((D // 128, n_out), jnp.float32)
        _compile(lambda x, w, s, z: q4_matmul(x, w, s, z, bm=DECODE_ROWS),
                 one_chip, x, ((D // 2, n_out), jnp.uint8), groups, groups)


@pytest.mark.parametrize("k_in,n_out,layers", [
    (D, K_HEADS * HEAD, LAYERS), (D, D, LAYERS), (D, FF, LAYERS),
    (FF, D, LAYERS), (D, VOCAB, 1)])
def test_stacked_q8_matmul_compiles(one_chip, k_in, n_out, layers):
    """Every published decode product reading its layer of the stack in
    place, at the tiles `q8_tiles` picks, within the default scoped VMEM;
    the LM head is a stack of one."""
    _compile(lambda x, w, s, i: q8_matmul(x, w, s, i), one_chip,
             ((DECODE_ROWS, k_in), jnp.bfloat16),
             ((layers, k_in, n_out), jnp.int8),
             ((layers, 1, n_out), jnp.float32), ((), jnp.int32))


def test_paged_decode_reads_q8_weights_in_place(one_chip):
    """The engine's paged decode step at published widths: no int8
    dynamic-slice (a per-layer weight copy) feeds a `quant_matmul` call, and
    every call's operands start (bf16 x, s8 weights, f32 scales), the order
    the benchmark's Q8 roofline reads."""
    from repro.common.registry import get_arch
    from repro.config import RuntimeConfig
    from repro.models import get_model
    from repro.quant import quant_spec
    from repro.serving.engine import _EngineExec
    from repro.sharding.param import abstract_params

    jax.config.update("jax_enable_compilation_cache", False)
    model = get_model(get_arch("carboncall-qwen2-7b"))
    rcfg = RuntimeConfig(use_pallas=True, interpret=False)

    def on_chip(spec):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            abstract_params(spec))
    params = on_chip(quant_spec(model.param_spec(), "q8"))
    pool = on_chip(model.paged_cache_spec(rcfg, POOL_BLOCKS, BLOCK))
    i32 = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
           for s in ((DECODE_ROWS, 1), (DECODE_ROWS,), (DECODE_ROWS, CHAIN))]
    ex = _EngineExec(model, rcfg, CHAIN * BLOCK, block_size=BLOCK)
    hlo = jax.jit(ex.decode_paged_impl, donate_argnums=(1,)).lower(
        params, pool, *i32).compile().as_text()

    defs = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = (.*)$", hlo, re.M))
    calls = {n: d for n, d in defs.items()
             if n.startswith("%quant_matmul") and " custom-call(" in d}
    assert len(calls) == 8      # q, k, v, o, gate, up, down; the LM head
    for name, d in calls.items():
        layout = re.search(r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=", d)
        assert re.findall(r"(\w+)\[", layout.group(1))[:3] == [
            "bf16", "s8", "f32"], (name, layout.group(1))
        args = re.search(r" custom-call\((.*?)\)", d).group(1)
        for op in re.findall(r"%[\w.-]+", args):
            src = defs.get(op, "")
            assert not (src.startswith("s8[") and "dynamic-slice" in
                        op + src), (name, op, src[:200])


def test_flash_attention_compiles(one_chip):
    S = 128                                       # the smoke run's bucket
    _compile(flash_attention_bnh, one_chip,
             ((1, N_HEADS, S, HEAD), jnp.bfloat16),
             ((1, K_HEADS, S, HEAD), jnp.bfloat16),
             ((1, K_HEADS, S, HEAD), jnp.bfloat16))


def test_topk_sim_compiles(one_chip):
    # ToolSelector pads its index to a multiple of 256 tools (a 64- or
    # 240-tool catalog) of 256-dim embeddings; queries pad to 8 rows
    _compile(lambda t, q: sim_scores(t, q, bt=256), one_chip,
             ((256, 256), jnp.float32), ((8, 256), jnp.float32))
