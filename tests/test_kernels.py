"""Kernel vs pure-jnp-oracle sweeps (shapes x dtypes), interpret mode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig, RuntimeConfig
from repro.models import get_model
from repro.quant import dense, quantize, quantize_tree
from repro.sharding.param import init_params
from repro.quant.qtensor import one_layer
from repro.kernels.quant_matmul import ops as qm_ops, ref as qm_ref
from repro.kernels.quant_matmul.quant_matmul import q8_matmul
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.paged_attention import ops as pa_ops, ref as pa_ref
from repro.kernels.ssd import ops as ssd_ops, ref as ssd_ref
from repro.kernels.topk_sim import ops as tk_ops, ref as tk_ref


KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("fmt", ["q8", "q4"])
@pytest.mark.parametrize("shape", [(128, 512, 256), (4, 256, 512),
                                   (64, 1024, 384), (8, 128, 128),
                                   (200, 384, 640)])
@pytest.mark.parametrize("xdtype", [jnp.bfloat16, jnp.float32])
def test_quant_matmul(fmt, shape, xdtype):
    M, K, N = shape
    seed = (M * 31 + K * 7 + N + (1 if fmt == "q4" else 0)) % (2 ** 31)
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, seed))
    x = jax.random.normal(k1, (M, K), xdtype)
    w = jax.random.normal(k2, (K, N), jnp.float32) * 0.05
    t = quantize(w, fmt)
    got = qm_ops.quant_matmul(x, t, interpret=True)
    want = qm_ref.qtensor_matmul_ref(x, t)
    gf = np.asarray(got, np.float32)
    wf = np.asarray(want, np.float32)
    rel = np.max(np.abs(gf - wf)) / max(np.max(np.abs(wf)), 1e-6)
    assert rel < 0.02, rel


@pytest.mark.parametrize("L,K,N", [(4, 256, 384), (1, 384, 1280)])
@pytest.mark.parametrize("M", [8, 40, 1024])
@pytest.mark.parametrize("xdtype", [jnp.bfloat16, jnp.float32])
def test_q8_matmul_stacked(L, K, N, M, xdtype):
    """The Q8 kernel reading layer l of an (L, K, N) stack in place, at the
    first, a middle and the last layer, against the oracle on the sliced
    layer; L = 1 is a weight stored as one matrix (the LM head). Decode rows
    (8), a verify window (40) and a prefill (1024) take their own tiles."""
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, L * K + N + M))
    x = jax.random.normal(k1, (M, K), xdtype)
    t = quantize(jax.random.normal(k2, (L, K, N), jnp.float32) * 0.05, "q8")
    for layer in sorted({0, L // 2, L - 1}):
        got = np.asarray(q8_matmul(x, t.q, t.scale, layer, interpret=True),
                         np.float32)
        want = np.asarray(qm_ref.q8_matmul_ref(x, t.q[layer], t.scale[layer]),
                          np.float32)
        if xdtype == jnp.float32:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        else:       # the two sum in different orders before rounding to bf16
            rel = np.max(np.abs(got - want)) / np.max(np.abs(want))
            assert rel < 0.01, (layer, rel)


@pytest.mark.parametrize("route", ["q8 kernel", "q8 xla", "q4 kernel",
                                   "q4 xla"])
def test_dense_on_stacked_view_equals_slice(route):
    """dense() on a stack that carries its layer index equals dense() on the
    layer cut out of the stack, on every route; the Q8 kernel counts the
    stacked view as read in place and the lone matrix as sliced."""
    fmt, path = route.split()
    L, K, N = 3, 256, 384
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 7))
    x = jax.random.normal(k1, (2, 4, K), jnp.bfloat16)
    t = quantize(jax.random.normal(k2, (L, K, N), jnp.float32) * 0.05, fmt)
    rcfg = (RuntimeConfig(use_pallas=True, interpret=True) if path == "kernel"
            else RuntimeConfig(use_pallas=False))
    for layer in range(L):
        stacked = dataclasses.replace(t, layer=jnp.int32(layer))
        got = dense(x, stacked, rcfg)
        want = dense(x, stacked.sliced(), rcfg)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    if fmt == "q8" and path == "kernel":
        qm_ops.quant_matmul.clear_cache()
        qm_ops.Q8_ROUTES.clear()
        dense(x, stacked, rcfg)
        dense(x, one_layer(stacked.sliced()), rcfg)
        assert qm_ops.Q8_ROUTES == {"stacked": 2}
        dense(x, stacked.sliced(), rcfg)
        assert qm_ops.Q8_ROUTES == {"stacked": 2, "sliced": 1}


def _greedy_run(model, params, rcfg, prompt, steps, bs=16):
    """Prefill `prompt` (B, S), prefill a 16-token suffix over it as a cached
    prefix, then `steps` greedy paged decode steps: every position's logits
    and the tokens chosen."""
    B, S = prompt.shape
    nb = 4
    cache = init_params(model.cache_spec(rcfg, B, nb * bs), KEY)
    logits, cache, _ = model.prefill(params, cache, {"tokens": prompt}, rcfg)
    suffix = jnp.argsort(prompt, axis=1)[:, :16].astype(jnp.int32)
    suf_logits, (k_suf, v_suf) = model.prefill_paged(
        params, {"tokens": suffix, "positions": S + jnp.arange(16)},
        cache["k"][:, :, :S], cache["v"][:, :, :S],
        jnp.full((B,), S, jnp.int32), rcfg)
    # row b's blocks are 1 + b * nb ... (block 0 is the reserved scratch)
    pool = init_params(model.paged_cache_spec(rcfg, B * nb + 1, bs), KEY)
    full = {"k": jnp.concatenate([cache["k"][:, :, :S], k_suf], axis=2),
            "v": jnp.concatenate([cache["v"][:, :, :S], v_suf], axis=2)}
    n = (S + 16) // bs
    for key, val in full.items():
        blocks = val.reshape(val.shape[0], B * n, bs, *val.shape[3:])
        ids = (1 + jnp.arange(B)[:, None] * nb + jnp.arange(n)).reshape(-1)
        pool[key] = pool[key].at[:, ids].set(blocks.astype(pool[key].dtype))
    tables = (1 + jnp.arange(B)[:, None] * nb + jnp.arange(nb)).astype(
        jnp.int32)
    rows, toks = [logits, suf_logits], []
    tok = jnp.argmax(suf_logits, axis=-1).astype(jnp.int32)
    lengths = jnp.full((B,), S + 16, jnp.int32)
    for _ in range(steps):
        toks.append(tok)
        logits, pool = model.decode_step_paged(
            params, pool, tok[:, None], lengths, tables, rcfg,
            seq_cap=nb * bs)
        rows.append(logits)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lengths = lengths + 1
    toks.append(tok)
    return (np.stack([np.asarray(r, np.float32) for r in rows]),
            np.stack([np.asarray(t) for t in toks]))


def test_stacked_q8_model_greedy_parity():
    """The model on the Q8 kernel route (each layer's weights read in place
    from the stack, interpret mode) against the XLA reference route: cold
    prefill, a suffix prefill over a cached prefix and paged decode steps
    give the same greedy tokens, and the kernel is handed no sliced layer."""
    cfg = ModelConfig(name="tiny-q8", family="transformer", num_layers=3,
                      d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
                      d_ff=256, vocab_size=384, qkv_bias=True)
    model = get_model(cfg)
    spec = model.param_spec()
    params = quantize_tree(init_params(spec, KEY), spec, "q8")
    prompt = jax.random.randint(jax.random.fold_in(KEY, 3), (2, 32), 0,
                                cfg.vocab_size, jnp.int32)
    qm_ops.quant_matmul.clear_cache()
    qm_ops.Q8_ROUTES.clear()
    got, got_toks = _greedy_run(
        model, params, RuntimeConfig(use_pallas=True, interpret=True), prompt,
        steps=4)
    assert qm_ops.Q8_ROUTES["sliced"] == 0 < qm_ops.Q8_ROUTES["stacked"]
    want, want_toks = _greedy_run(
        model, params, RuntimeConfig(use_pallas=False), prompt, steps=4)
    np.testing.assert_array_equal(got_toks, want_toks)
    rel = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert rel < 0.02, rel


@pytest.mark.parametrize(
    "B,Sq,Skv,N,K,H,causal,window,cap",
    [
        (2, 256, 256, 4, 2, 64, True, 0, 0.0),
        (1, 256, 256, 8, 8, 128, True, 64, 50.0),   # gemma2-style local+cap
        (2, 128, 256, 4, 4, 64, False, 0, 0.0),     # cross-attn style
        (1, 512, 512, 4, 1, 32, True, 0, 0.0),      # MQA
        (2, 128, 128, 2, 2, 256, True, 0, 30.0),
    ])
def test_flash_attention(B, Sq, Skv, N, K, H, causal, window, cap):
    kq, kk, kv = jax.random.split(jax.random.fold_in(KEY, Sq * Skv + N), 3)
    q = jax.random.normal(kq, (B, Sq, N, H), jnp.bfloat16)
    k = jax.random.normal(kk, (B, Skv, K, H), jnp.bfloat16)
    v = jax.random.normal(kv, (B, Skv, K, H), jnp.bfloat16)
    off = Skv - Sq if causal else 0
    got = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 cap=cap, q_offset=off, interpret=True)
    want = fa_ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                      cap=cap, q_offset=off)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < 0.03, err


@pytest.mark.parametrize("B,S,H,P,G,N,Q", [
    (2, 256, 4, 64, 1, 128, 128),
    (1, 128, 8, 32, 2, 64, 64),
    (2, 64, 4, 16, 1, 32, 32),
    (1, 256, 2, 64, 1, 16, 64),
])
def test_ssd(B, S, H, P, G, N, Q):
    ks = jax.random.split(jax.random.fold_in(KEY, S * H + N), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    y1, f1 = ssd_ops.ssd(x, dt, A, Bm, Cm, chunk=Q, interpret=True)
    y2, f2 = ssd_ref.ssd_ref(x, dt, A, Bm, Cm, chunk=Q)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 0.05
    assert float(jnp.max(jnp.abs(f1 - f2))) < 0.05


def _paged_case(B, N, K, H, bs, nb, seed, lengths=None):
    """Random pools + permuted block tables; dead table slots point at the
    reserved scratch block 0 and rows vary in fill level."""
    num_blocks = nb * B + 2
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    q = jax.random.normal(ks[0], (B, 1, N, H), jnp.float32)
    kp = jax.random.normal(ks[1], (num_blocks, bs, K, H), jnp.float32)
    vp = jax.random.normal(ks[2], (num_blocks, bs, K, H), jnp.float32)
    bt = np.zeros((B, nb), np.int32)
    lens = np.zeros((B,), np.int32)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(np.arange(1, num_blocks))
    for b in range(B):
        lens[b] = (int(rng.integers(1, nb * bs)) if lengths is None
                   else int(lengths[b]))
        used = -(-int(lens[b]) // bs)
        bt[b, :used] = perm[b * nb:b * nb + used]
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lens)


def _quantize_pool(pool):
    """Symmetric per-(block, pos, head) int8, matching requant_cache."""
    s = jnp.maximum(jnp.max(jnp.abs(pool), axis=-1), 1e-8) / 127.0
    return (jnp.round(pool / s[..., None]).astype(jnp.int8),
            s.astype(jnp.float32))


@pytest.mark.parametrize(
    "B,N,K,H,bs,nb,cap,window,splits",
    [
        (2, 4, 2, 64, 16, 4, 0.0, 0, 1),
        (3, 8, 8, 32, 32, 3, 0.0, 0, 1),      # MHA (K == N)
        (1, 4, 1, 128, 16, 8, 50.0, 0, 1),    # softcap, deep chain
        (2, 4, 2, 64, 16, 4, 0.0, 24, 1),     # sliding window
        (2, 4, 2, 64, 16, 8, 0.0, 0, 2),      # split-K flash decode
        (1, 4, 1, 128, 16, 8, 50.0, 0, 4),    # split-K + softcap
    ])
def test_paged_attention(B, N, K, H, bs, nb, cap, window, splits):
    """Block-table walk vs gather-then-dense-decode oracle."""
    q, kp, vp, bt, lengths = _paged_case(B, N, K, H, bs, nb, B * 31 + H)
    got = pa_ops.paged_decode_attention(
        q, kp, vp, bt, lengths,
        cap=cap, window=window, num_splits=splits, interpret=True)
    want = pa_ref.paged_attention_ref(
        q, kp, vp, bt, lengths, cap=cap, window=window)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


@pytest.mark.parametrize(
    "B,N,K,H,bs,nb,cap,window,splits",
    [
        (2, 4, 2, 64, 16, 4, 0.0, 0, 1),      # fused dequant, single chain
        (3, 8, 8, 32, 32, 3, 0.0, 0, 1),      # MHA
        (1, 4, 1, 128, 16, 8, 50.0, 0, 2),    # softcap across split boundary
        (2, 4, 2, 64, 16, 4, 0.0, 24, 1),     # sliding window
        (3, 4, 2, 64, 16, 9, 0.0, 0, 3),      # ragged lengths vs split-K
    ])
def test_paged_attention_int8(B, N, K, H, bs, nb, cap, window, splits):
    """Fused-dequant int8 kernel vs `paged_attention_ref`'s dequant-after-
    gather. The ref dequantizes through bf16 before attention while the
    kernel dequantizes in f32 inside VMEM, so the tolerance is dominated by
    the ref's bf16 rounding — loose relative to the f32 sweep above but far
    inside the ~1/127 quantization grid itself."""
    q, kf, vf, bt, lengths = _paged_case(B, N, K, H, bs, nb, B * 17 + H + nb)
    kp, ksc = _quantize_pool(kf)
    vp, vsc = _quantize_pool(vf)
    got = pa_ops.paged_decode_attention(
        q, kp, vp, bt, lengths, k_scale=ksc, v_scale=vsc,
        cap=cap, window=window, num_splits=splits, interpret=True)
    want = pa_ref.paged_attention_ref(
        q, kp, vp, bt, lengths, k_scale=ksc, v_scale=vsc,
        cap=cap, window=window)
    assert float(jnp.max(jnp.abs(got - want))) < 0.02


def test_paged_attention_int8_scratch_rows():
    """Rows parked almost entirely on the scratch block 0 (length 1) next to
    a full row, with ragged lengths straddling the split-K boundary: the
    untouched splits must merge as exact zeros, not NaNs."""
    B, N, K, H, bs, nb = 4, 4, 2, 64, 8, 6
    # lengths: 1 (scratch-dominated), exactly one split (16), one past the
    # boundary (17), and full (48)
    q, kf, vf, bt, lengths = _paged_case(
        B, N, K, H, bs, nb, 101, lengths=[1, 16, 17, 48])
    kp, ksc = _quantize_pool(kf)
    vp, vsc = _quantize_pool(vf)
    for splits in (1, 3):
        got = pa_ops.paged_decode_attention(
            q, kp, vp, bt, lengths, k_scale=ksc, v_scale=vsc,
            num_splits=splits, interpret=True)
        want = pa_ref.paged_attention_ref(
            q, kp, vp, bt, lengths, k_scale=ksc, v_scale=vsc)
        assert bool(jnp.all(jnp.isfinite(got)))
        assert float(jnp.max(jnp.abs(got - want))) < 0.02


@pytest.mark.parametrize("n_tools,d,m,k", [(2048, 64, 3, 5), (512, 128, 1, 8),
                                           (1024, 256, 7, 16)])
def test_topk_sim(n_tools, d, m, k):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, n_tools + d))
    tools = jax.random.normal(k1, (n_tools, d))
    tools = tools / jnp.linalg.norm(tools, axis=-1, keepdims=True)
    qs = jax.random.normal(k2, (m, d))
    s1, i1 = tk_ops.topk_tools(tools, qs, k=k, interpret=True)
    qn = qs / jnp.linalg.norm(qs, axis=-1, keepdims=True)
    s2, i2 = tk_ref.topk_tools_ref(tools, qn, k)
    assert bool(jnp.all(i1 == i2))
    assert float(jnp.max(jnp.abs(s1 - s2))) < 1e-5
