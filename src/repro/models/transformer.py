"""Decoder-only transformer LM covering the dense, MoE, and VLM families.

Design notes:
  * Layers are stacked on a leading dim and executed with lax.scan — HLO size
    is O(1) in depth (95-layer deepseek compiles as fast as 6-layer whisper).
  * Architectures with a layer-type *pattern* (gemma2's local/global
    alternation) scan over groups of `pattern` layers; within a group the
    members run unrolled with static window sizes, so sliding-window layers
    keep a static mask.
  * Forward returns hidden states; the LM head is applied separately
    (training uses chunked cross-entropy that never materializes full logits).
  * decode_step writes one token into a (layers, B, Smax, K, H) cache whose
    sequence dim is sharded over `model` (flash-decode layout); per-row
    `lengths` supports ragged continuous batching.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.config import ModelConfig, RuntimeConfig
from repro.models import layers as L
from repro.models import blocks as B_
from repro.models.moe import moe_spec, moe_apply
from repro.quant import dense
from repro.quant.qtensor import layer_inputs, layer_params, one_layer
from repro.sharding.param import ParamDef
from repro.sharding.rules import constrain


# ---------------------------------------------------------------------------
# Spec
# ---------------------------------------------------------------------------


def param_spec(cfg: ModelConfig):
    Lc = cfg.num_layers
    d, V = cfg.d_model, cfg.vocab_size
    layer = {
        "attn": B_.attn_spec(cfg, (Lc,), ("layers",)),
        "norms": B_.block_norms_spec(cfg, (Lc,), ("layers",)),
    }
    if cfg.family == "moe":
        layer["moe"] = moe_spec(cfg, (Lc,), ("layers",))
    else:
        layer["mlp"] = B_.mlp_spec(cfg, (Lc,), ("layers",))
    spec = {
        "embed": ParamDef((V, d), ("vocab", "embed"), init="embed"),
        "layers": layer,
        "final_norm": ParamDef((d,), (None,), init="zeros"),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = ParamDef((d, V), ("embed", "vocab"))
    return spec


def _pattern(cfg: ModelConfig) -> int:
    return cfg.local_global_pattern or 1


def window_for(cfg: ModelConfig, member: int) -> int:
    """Static sliding window for the member-th layer within a pattern group."""
    p = _pattern(cfg)
    if p == 1:
        return cfg.sliding_window
    # gemma2-style: members 0..p-2 are local, the last member is global
    return cfg.sliding_window if member < p - 1 else 0


# ---------------------------------------------------------------------------
# Rope helpers
# ---------------------------------------------------------------------------


def rope_for(cfg: ModelConfig, positions, B: int, S: int):
    H = cfg.resolved_head_dim
    if cfg.use_mrope:
        assert positions is not None and positions.ndim == 3, \
            "M-RoPE archs need positions (3, B, S)"
        return L.mrope_cos_sin(positions, H, cfg.rope_theta, cfg.mrope_sections)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    return L.rope_cos_sin(positions, H, cfg.rope_theta)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(params, batch, cfg: ModelConfig):
    tokens = batch["tokens"]
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.bfloat16)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)
        Pn = pe.shape[1]
        x = jnp.concatenate([pe, x[:, Pn:]], axis=1)
    if cfg.name.startswith("gemma"):
        x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
    return constrain(x, ("act_batch", "act_seq", "act_embed"))


def unembed(params, h, cfg: ModelConfig, rcfg):
    if cfg.tie_embeddings:
        logits = jax.lax.dot_general(
            h, params["embed"].astype(h.dtype),
            (((h.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        logits = dense(h, one_layer(params["lm_head"]),
                       rcfg).astype(jnp.float32)
    return L.softcap(logits, cfg.final_logit_softcap)


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------


def _layer_decode(p_i, x, cache_i, lengths, cfg, rcfg, cos, sin, window):
    n = p_i["norms"]
    h = L.rms_norm(x, n["pre_attn"], cfg.norm_eps)
    a, cache_i = B_.attn_decode_apply(
        p_i["attn"], h, cfg, rcfg, cos=cos, sin=sin,
        cache_i=cache_i, lengths=lengths, window=window)
    if "post_attn" in n:
        a = L.rms_norm(a, n["post_attn"], cfg.norm_eps)
    x = x + a
    h = L.rms_norm(x, n["pre_mlp"], cfg.norm_eps)
    if cfg.family == "moe":
        m, _ = moe_apply(p_i["moe"], h, cfg, rcfg)
    else:
        m = B_.mlp_apply(p_i["mlp"], h, cfg, rcfg)
    if "post_mlp" in n:
        m = L.rms_norm(m, n["post_mlp"], cfg.norm_eps)
    x = x + m
    return x, cache_i


# ---------------------------------------------------------------------------
# Cache (bf16 or int8 with per-(pos, head) scales)
# ---------------------------------------------------------------------------


def cache_spec(cfg: ModelConfig, rcfg: RuntimeConfig, batch: int, max_seq: int):
    Lc, K, H = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    log = ("layers", "cache_batch", "cache_seq", "cache_heads", None)
    if rcfg.kv_cache_dtype == "int8":
        slog = ("layers", "cache_batch", "cache_seq", "cache_heads")
        return {
            "k": ParamDef((Lc, batch, max_seq, K, H), log, init="zeros", dtype="int8"),
            "v": ParamDef((Lc, batch, max_seq, K, H), log, init="zeros", dtype="int8"),
            "k_scale": ParamDef((Lc, batch, max_seq, K), slog, init="zeros", dtype="fp32"),
            "v_scale": ParamDef((Lc, batch, max_seq, K), slog, init="zeros", dtype="fp32"),
        }
    return {
        "k": ParamDef((Lc, batch, max_seq, K, H), log, init="zeros", dtype="bf16"),
        "v": ParamDef((Lc, batch, max_seq, K, H), log, init="zeros", dtype="bf16"),
    }


def paged_cache_spec(cfg: ModelConfig, rcfg: RuntimeConfig, num_blocks: int,
                     block_size: int):
    """Paged pool layout: (layers, num_blocks, block_size, K, H) per leaf.
    Blocks are position-agnostic (any block can hold any 16-token stripe of
    any sequence), so only the head dim carries a sharding axis — the block
    dim is the unit of allocation and must stay whole per shard."""
    Lc, K, H = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    log = ("layers", None, None, "cache_heads", None)
    slog = ("layers", None, None, "cache_heads")
    if rcfg.kv_cache_dtype == "int8":
        return {
            "k": ParamDef((Lc, num_blocks, block_size, K, H), log,
                          init="zeros", dtype="int8"),
            "v": ParamDef((Lc, num_blocks, block_size, K, H), log,
                          init="zeros", dtype="int8"),
            "k_scale": ParamDef((Lc, num_blocks, block_size, K), slog,
                                init="zeros", dtype="fp32"),
            "v_scale": ParamDef((Lc, num_blocks, block_size, K), slog,
                                init="zeros", dtype="fp32"),
        }
    return {
        "k": ParamDef((Lc, num_blocks, block_size, K, H), log,
                      init="zeros", dtype="bf16"),
        "v": ParamDef((Lc, num_blocks, block_size, K, H), log,
                      init="zeros", dtype="bf16"),
    }


def paged_block_bytes(cfg: ModelConfig, block_size: int,
                      kv_cache_dtype: str = "bf16") -> int:
    """Bytes one pool block occupies across all layers (k + v leaves, plus
    the fp32 scale stripes for int8). This is the capacity math behind the
    engine's int8 auto-sizing: at the same byte budget an int8 pool fits
    2H/(H+4) ~ 1.9x the bf16 block count (H = head dim; the +4 is the two
    fp32 scales amortized over k and v)."""
    Lc, K, H = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    if kv_cache_dtype == "int8":
        return Lc * block_size * K * (2 * H + 2 * 4)
    return Lc * block_size * K * (2 * 2 * H)


def dequant_cache(cache_i):
    """Per-layer cache dict -> (k, v) bf16 views (XLA fuses the dequant into
    the attention matmuls; HBM traffic stays int8)."""
    if "k_scale" in cache_i:
        k = cache_i["k"].astype(jnp.float32) * cache_i["k_scale"][..., None]
        v = cache_i["v"].astype(jnp.float32) * cache_i["v_scale"][..., None]
        return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    return cache_i["k"], cache_i["v"]


def requant_cache(cache_i, k, v):
    if "k_scale" not in cache_i:
        return {"k": k, "v": v}
    ks = jnp.maximum(jnp.max(jnp.abs(k), axis=-1), 1e-8) / 127.0
    vs = jnp.maximum(jnp.max(jnp.abs(v), axis=-1), 1e-8) / 127.0
    return {
        "k": jnp.round(k / ks[..., None]).astype(jnp.int8),
        "v": jnp.round(v / vs[..., None]).astype(jnp.int8),
        "k_scale": ks.astype(jnp.float32),
        "v_scale": vs.astype(jnp.float32),
    }


def quantize_kv_for_cache(cache_has_scale: bool, k, v):
    if not cache_has_scale:
        return {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}
    return requant_cache({"k_scale": True}, k, v)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _group_tree(tree, groups: int, gs: int):
    return jax.tree.map(lambda a: a.reshape(groups, gs, *a.shape[1:]), tree)


def forward(params, batch, cfg: ModelConfig, rcfg: RuntimeConfig, *,
            collect_kv: bool = False, train: bool = False):
    """-> (hidden (B,S,d), stacked (k,v) or None, aux scalar)."""
    x = embed_tokens(params, batch, cfg)
    Bb, S, _ = x.shape
    cos, sin = rope_for(cfg, batch.get("positions"), Bb, S)
    gs = _pattern(cfg)
    groups = cfg.num_layers // gs
    layers = params["layers"]
    xs = (jnp.arange(groups), _group_tree(layer_inputs(layers), groups, gs))

    def body_moe_aware(carry, xs_g):
        x, aux = carry
        g, p_g = xs_g
        # SP constraint on the block INPUT as well as its output: without it
        # the backward cotangent of the residual enters the layer transpose
        # replicated and every dgrad partial resolves with a full (B,S,d)
        # all-reduce; anchored at both ends GSPMD emits reduce-scatters
        # (half the bytes) and keeps the saved residual S-sharded.
        x = constrain(x, ("act_batch", "act_seq", "act_embed"))
        kvs = []
        for m in range(gs):
            p_i = layer_params(layers, jax.tree.map(lambda a: a[m], p_g),
                               g * gs + m)
            n = p_i["norms"]
            h = L.rms_norm(x, n["pre_attn"], cfg.norm_eps)
            a, kv = B_.attn_apply(p_i["attn"], h, cfg, rcfg, cos=cos, sin=sin,
                                  window=window_for(cfg, m))
            if "post_attn" in n:
                a = L.rms_norm(a, n["post_attn"], cfg.norm_eps)
            x = x + a
            h = L.rms_norm(x, n["pre_mlp"], cfg.norm_eps)
            if cfg.family == "moe":
                mm, aux_i = moe_apply(p_i["moe"], h, cfg, rcfg)
                aux = aux + aux_i
            else:
                mm = B_.mlp_apply(p_i["mlp"], h, cfg, rcfg)
            if "post_mlp" in n:
                mm = L.rms_norm(mm, n["post_mlp"], cfg.norm_eps)
            x = x + mm
            x = constrain(x, ("act_batch", "act_seq", "act_embed"))
            kvs.append(kv)
        out = jax.tree.map(lambda *xs: jnp.stack(xs), *kvs) if collect_kv else None
        return (x, aux), out

    scan_body = body_moe_aware
    if train and rcfg.remat_policy != "none":
        policy = None
        if rcfg.remat_policy == "save_dots":
            policy = jax.checkpoint_policies.checkpoint_dots
        scan_body = jax.checkpoint(scan_body, policy=policy,
                                   prevent_cse=False)

    if rcfg.scan_layers:
        (x, aux), kv_stack = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.float32)), xs)
    else:
        # unrolled (HLO grows with depth): used by the analytic-flops
        # validation tests, where scan would hide per-layer cost
        carry = (x, jnp.zeros((), jnp.float32))
        kvs = []
        for g in range(groups):
            carry, kv = scan_body(carry, jax.tree.map(lambda a: a[g], xs))
            kvs.append(kv)
        x, aux = carry
        kv_stack = (jax.tree.map(lambda *xs: jnp.stack(xs), *kvs)
                    if collect_kv else None)
    if collect_kv:
        # (groups, gs, B, S, K, H) -> (L, B, S, K, H)
        kv_stack = jax.tree.map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), kv_stack)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, kv_stack, aux


def prefill(params, cache, batch, cfg: ModelConfig, rcfg: RuntimeConfig):
    """Fill the cache from a full prompt; returns last-position logits.

    batch["tokens"]: (B, S_prompt) — assumed right-aligned dense (length = S).
    """
    h, kv, _ = forward(params, batch, cfg, rcfg, collect_kv=True)
    k, v = kv
    Smax = cache["k"].shape[2]
    S = k.shape[2]
    has_scale = "k_scale" in cache
    entry = quantize_kv_for_cache(has_scale, k, v)
    new_cache = {}
    for key, val in entry.items():
        pad = [(0, 0)] * val.ndim
        pad[2] = (0, Smax - S)
        new_cache[key] = jnp.pad(val, pad).astype(cache[key].dtype)
    logits = unembed(params, h[:, -1:, :], cfg, rcfg)[:, 0]
    lengths = jnp.full((k.shape[1],), S, jnp.int32)
    return logits, new_cache, lengths


def _prefill_window(params, batch, prefix_k, prefix_v, prefix_lens,
                    cfg: ModelConfig, rcfg: RuntimeConfig, *,
                    need_logits: bool, all_logits: bool = False):
    """Shared body for `prefill_paged` / `prefill_chunk` / `verify_paged`:
    run a token window over a cached (gathered) prefix, returning the
    window's KV stacks and — only when `need_logits` — the last-position
    logits ((B, S, V) every-position logits with `all_logits`, the
    speculative-decode verify shape). Middle chunks of a chunked prefill
    skip the unembed matmul entirely. `batch["positions"]` is (S,) uniform
    across rows or (B, S) per-row absolute positions."""
    assert _pattern(cfg) == 1, "paged prefill: local/global patterns unsupported"
    assert not cfg.use_mrope, "paged prefill: M-RoPE unsupported"
    x = embed_tokens(params, batch, cfg)
    Bb, S, _ = x.shape
    q_pos = batch["positions"]
    cos, sin = rope_for(cfg, q_pos if q_pos.ndim == 2 else q_pos[None, :],
                        Bb, S)

    layers = params["layers"]

    def body(x, xs):
        i, p_i, k_pre, v_pre = xs
        p_i = layer_params(layers, p_i, i)
        x = constrain(x, ("act_batch", "act_seq", "act_embed"))
        n = p_i["norms"]
        h = L.rms_norm(x, n["pre_attn"], cfg.norm_eps)
        q, k, v = B_.qkv_proj(p_i["attn"], h, cfg, rcfg, cos, sin)
        o = L.prefix_attention(q, k_pre, v_pre, k, v, prefix_lens, q_pos,
                               window=window_for(cfg, 0),
                               cap=cfg.attn_logit_softcap)
        a = dense(o.reshape(Bb, S, -1), p_i["attn"]["wo"], rcfg)
        if "post_attn" in n:
            a = L.rms_norm(a, n["post_attn"], cfg.norm_eps)
        x = x + a
        h = L.rms_norm(x, n["pre_mlp"], cfg.norm_eps)
        if cfg.family == "moe":
            m, _ = moe_apply(p_i["moe"], h, cfg, rcfg)
        else:
            m = B_.mlp_apply(p_i["mlp"], h, cfg, rcfg)
        if "post_mlp" in n:
            m = L.rms_norm(m, n["post_mlp"], cfg.norm_eps)
        x = x + m
        return x, (k, v)

    x, (k_suf, v_suf) = jax.lax.scan(
        body, x, (jnp.arange(cfg.num_layers), layer_inputs(layers),
                  prefix_k, prefix_v))
    if not need_logits:
        return None, (k_suf, v_suf)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if all_logits:
        return unembed(params, x, cfg, rcfg), (k_suf, v_suf)
    logits = unembed(params, x[:, -1:, :], cfg, rcfg)[:, 0]
    return logits, (k_suf, v_suf)


def prefill_paged(params, batch, prefix_k, prefix_v, prefix_lens,
                  cfg: ModelConfig, rcfg: RuntimeConfig):
    """Suffix prefill over a cached prompt prefix (paged prefix-cache hit).

    batch["tokens"]: (B, S_suf) left-padded suffix rows — row b's real tokens
    sit in the last (total - prefix_lens[b]) slots of the bucket-wide suffix.
    batch["positions"]: (S_suf,) absolute positions, uniform across rows
    (every row in an admission batch is padded to the same total length).
    prefix_k/v: (L, B, P, K, H) prefix KV gathered (and dequantized) from the
    block pool, valid where the absolute position is < prefix_lens[b].

    Returns (last-position logits (B, V), suffix (k, v) stacks each
    (L, B, S_suf, K, H) for the engine to scatter into the pool). Restricted
    to pattern-1, non-M-RoPE families — the engine falls back to the dense
    layout otherwise.
    """
    return _prefill_window(params, batch, prefix_k, prefix_v, prefix_lens,
                           cfg, rcfg, need_logits=True)


def prefill_chunk(params, batch, prefix_k, prefix_v, prefix_lens,
                  cfg: ModelConfig, rcfg: RuntimeConfig, *,
                  need_logits: bool):
    """One window of a chunked prefill: the tokens in `batch` extend a
    partially-prefilled prompt whose first `prefix_lens[b]` positions already
    sit in the block pool (the parked chain from earlier chunks — the same
    shape as a prefix-cache hit, which is what makes chunking reuse the CoW
    machinery unchanged). Numerically identical to running the same window
    inside one monolithic `prefill_paged` call, so temperature-0 streams stay
    token-identical chunked vs. unchunked. Middle windows pass
    `need_logits=False` and get `(None, (k, v))` — only the final window pays
    for the unembed."""
    return _prefill_window(params, batch, prefix_k, prefix_v, prefix_lens,
                           cfg, rcfg, need_logits=need_logits)


def verify_paged(params, batch, prefix_k, prefix_v, prefix_lens,
                 cfg: ModelConfig, rcfg: RuntimeConfig):
    """Speculative-decode verify: one batched forward over each row's k+1
    candidate window (the last accepted token plus k Q4 drafts), continuing
    from the row's canonical cached prefix.

    batch["tokens"]: (B, W) candidate windows; batch["positions"]: (B, W)
    per-row absolute positions arange(len_b, len_b + W) — rows continue from
    their own lengths, unlike admission prefill's uniform positions.
    prefix_k/v / prefix_lens: as in `prefill_paged` (gathered canonical KV,
    valid below prefix_lens[b]).

    Returns (logits (B, W, V) at every window position, window (k, v) stacks
    each (L, B, W, K, H)). Greedy argmax over logits[:, j] is exactly what
    plain Q8 decode would emit after accepting window[:, :j+1], which is the
    temperature-0 acceptance rule's correctness argument."""
    return _prefill_window(params, batch, prefix_k, prefix_v, prefix_lens,
                           cfg, rcfg, need_logits=True, all_logits=True)


def decode_step_paged(params, pool, tokens, lengths, block_tables,
                      cfg: ModelConfig, rcfg: RuntimeConfig, *, seq_cap: int):
    """One token per row against the paged block pool. tokens: (B,1);
    lengths: (B,) logical fill counts; block_tables: (B, nb) physical block
    ids per logical block (0 = reserved scratch). `seq_cap` is the engine's
    max_seq — writes at or past it are dropped, matching the dense path."""
    assert _pattern(cfg) == 1 and not cfg.use_mrope
    x = embed_tokens(params, {"tokens": tokens}, cfg)
    Bb = x.shape[0]
    cos, sin = rope_for(cfg, lengths[:, None], Bb, 1)

    layers = params["layers"]

    def body(x, xs):
        i, p_i, c_i = xs
        p_i = layer_params(layers, p_i, i)
        n = p_i["norms"]
        h = L.rms_norm(x, n["pre_attn"], cfg.norm_eps)
        a, c_i2 = B_.attn_decode_paged_apply(
            p_i["attn"], h, cfg, rcfg, cos=cos, sin=sin, pool_i=c_i,
            lengths=lengths, block_tables=block_tables, seq_cap=seq_cap,
            window=window_for(cfg, 0))
        if "post_attn" in n:
            a = L.rms_norm(a, n["post_attn"], cfg.norm_eps)
        x = x + a
        h = L.rms_norm(x, n["pre_mlp"], cfg.norm_eps)
        if cfg.family == "moe":
            m, _ = moe_apply(p_i["moe"], h, cfg, rcfg)
        else:
            m = B_.mlp_apply(p_i["mlp"], h, cfg, rcfg)
        if "post_mlp" in n:
            m = L.rms_norm(m, n["post_mlp"], cfg.norm_eps)
        x = x + m
        return x, c_i2

    x, new_pool = jax.lax.scan(
        body, x, (jnp.arange(cfg.num_layers), layer_inputs(layers), pool))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x, cfg, rcfg)[:, 0]
    return logits, new_pool


def decode_step(params, cache, tokens, lengths, cfg: ModelConfig,
                rcfg: RuntimeConfig, positions=None):
    """One token per row. tokens: (B,1); lengths: (B,) cache fill counts."""
    x = embed_tokens(params, {"tokens": tokens}, cfg)
    Bb = x.shape[0]
    pos = positions if positions is not None else lengths[None, :, None] \
        if cfg.use_mrope else lengths[:, None]
    if cfg.use_mrope and positions is None:
        pos = jnp.broadcast_to(lengths[None, :, None], (3, Bb, 1))
    cos, sin = rope_for(cfg, pos, Bb, 1)
    gs = _pattern(cfg)
    groups = cfg.num_layers // gs
    layers = params["layers"]
    cache_g = _group_tree(cache, groups, gs)

    def body(x, xs):
        g, p_g, c_g = xs
        new_c = []
        for m in range(gs):
            p_i = layer_params(layers, jax.tree.map(lambda a: a[m], p_g),
                               g * gs + m)
            c_i = jax.tree.map(lambda a: a[m], c_g)
            x, c_i2 = _layer_decode(p_i, x, c_i, lengths, cfg, rcfg, cos, sin,
                                    window_for(cfg, m))
            new_c.append(c_i2)
        stacked = jax.tree.map(lambda *xs_: jnp.stack(xs_), *new_c)
        return x, stacked

    x, new_cache = jax.lax.scan(
        body, x, (jnp.arange(groups),
                  _group_tree(layer_inputs(layers), groups, gs), cache_g))
    new_cache = jax.tree.map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), new_cache)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x, cfg, rcfg)[:, 0]
    return logits, new_cache
