"""Quantization substrate: formats, tree transforms, abstract/concrete parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig
from repro.models import get_model
from repro.quant import (QTensor, quantize, dequantize, quantize_tree,
                         quant_spec, dense)
from repro.quant.qtensor import unpack_q4
from repro.sharding.param import init_params, abstract_params, ParamDef

CFG = ModelConfig(name="tiny", family="transformer", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256)


@pytest.mark.parametrize("fmt,tol", [("q8", 0.012), ("q4", 0.12)])
def test_roundtrip_error(fmt, tol):
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 128)) * 0.3
    t = quantize(w, fmt)
    back = dequantize(t, jnp.float32)
    err = float(jnp.max(jnp.abs(back - w)))
    assert err < tol * float(jnp.max(jnp.abs(w)))


def test_q4_pack_unpack_identity():
    q = jax.random.randint(jax.random.PRNGKey(1), (64, 32), 0, 16).astype(jnp.uint8)
    packed = (q[0::2, :] | (q[1::2, :] << 4)).astype(jnp.uint8)
    assert (unpack_q4(packed) == q).all()


def test_dense_handles_qtensor():
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 256), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(3), (256, 64)) * 0.1
    t = quantize(w, "q8")
    got = dense(x, t)
    want = x.astype(jnp.float32) @ dequantize(t, jnp.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_quant_spec_matches_quantize_tree_structure():
    """Abstract quantized specs (dry-run) and concrete quantized params must
    have identical tree structure — the serving dry-run stands in for real
    checkpoints."""
    model = get_model(CFG)
    spec = model.param_spec()
    params = init_params(spec, jax.random.PRNGKey(0))
    for fmt in ("q8", "q4"):
        qs = quant_spec(spec, fmt)
        qp = quantize_tree(params, spec, fmt)
        abstract = abstract_params(qs)
        s1 = jax.tree_util.tree_structure(abstract)
        s2 = jax.tree_util.tree_structure(qp)
        assert s1 == s2, (fmt, s1, s2)


def test_embedding_not_quantized():
    model = get_model(CFG)
    spec = model.param_spec()
    qs = quant_spec(spec, "q8")
    assert isinstance(qs["embed"], ParamDef)          # lookup table stays bf16
    assert isinstance(qs["lm_head"], QTensor)         # head matmul quantizes


def test_bytes_reduction():
    model = get_model(CFG)
    spec = model.param_spec()
    params = init_params(spec, jax.random.PRNGKey(0))
    def nbytes(tree):
        return sum(q.nbytes() if isinstance(q, QTensor) else q.nbytes
                   for q in jax.tree.leaves(
                       tree, is_leaf=lambda x: isinstance(x, QTensor)))
    b16 = nbytes(params)
    b8 = nbytes(quantize_tree(params, spec, "q8"))
    b4 = nbytes(quantize_tree(params, spec, "q4"))
    assert b8 < 0.75 * b16                        # embed stays bf16
    assert b4 < b8


@pytest.mark.parametrize("max_piece", [1 << 26, 2048])
def test_build_variants_matches_quantize_tree(max_piece, monkeypatch):
    """The piece-by-piece builder never holds a bf16 tree, but the bf16
    pieces it draws, assembled, quantize leaf for leaf to its Q8 and Q4
    trees. A small piece limit also splits the 2-D leaves (LM head,
    embedding) along their output dim, as the 152064-vocab head is."""
    from repro.common.registry import get_arch
    from repro.configs.reduced import reduce_config
    from repro.quant import build_variants, qtensor
    monkeypatch.setattr(qtensor, "MAX_PIECE_ELEMS", max_piece)
    spec = get_model(reduce_config(get_arch("carboncall-qwen2-7b"))).param_spec()
    built = build_variants(spec, jax.random.PRNGKey(3), ("bf16", "q8", "q4"))
    assert jax.tree.structure(built["bf16"]) == jax.tree.structure(
        init_params(spec, jax.random.PRNGKey(0)))
    for fmt in ("q8", "q4"):
        want = quantize_tree(built["bf16"], spec, fmt)
        assert jax.tree.structure(want) == jax.tree.structure(built[fmt])
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(built[fmt])):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # leaves no format quantizes are one array shared by the variants
    assert built["q8"]["embed"] is built["q4"]["embed"]
    assert built["q8"]["embed"] is built["bf16"]["embed"]
