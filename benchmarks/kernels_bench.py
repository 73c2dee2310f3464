"""Kernel microbenches (interpret-mode wall time is NOT TPU performance —
the derived column reports the roofline-model numbers that matter: bytes
moved per output and the theoretical speedup vs the bf16 path on v5e).

    PYTHONPATH=src:. python benchmarks/kernels_bench.py                # CPU
    PYTHONPATH=src:. python benchmarks/kernels_bench.py --chip OUT.json  # TPU

`--chip` times the compiled Q8 kernel at the published Qwen2-7B shapes
(`q8_chip_sweep`) and writes every row to OUT.json.
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp

import numpy as np

from benchmarks.common import emit
from repro.common.hardware import TPU_V5E
from repro.quant import quantize
from repro.kernels.quant_matmul import ops as qm_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.paged_attention import ops as pa_ops, ref as pa_ref
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.topk_sim import ops as tk_ops


def timed(name: str, fn: Callable, *, repeats: int = 3, derived_fn=None):
    """Mean wall time of `fn()` over `repeats` calls after one warm-up
    call, each waited on with `block_until_ready`; emitted as a CSV row."""
    jax.block_until_ready(fn())              # warm-up / compile
    t0 = time.perf_counter()  # cc-lint: disable=CC001 -- real wall-clock is the measurement here
    out = None
    for _ in range(repeats):
        out = jax.block_until_ready(fn())
    us = (time.perf_counter() - t0) / repeats * 1e6  # cc-lint: disable=CC001 -- real wall-clock is the measurement here
    emit(name, us, derived_fn(out) if derived_fn else "")
    return out


def paged_attention_bench(quiet: bool = False):
    """Fused-dequant paged decode attention, bf16 vs int8 pools.

    The timed body is `paged_decode_attention` itself — the Pallas kernel
    (split-K flash decode, scales fused in-VMEM for int8), NOT the
    `paged_attention_ref` gather fallback — so the roofline deriveds and the
    parity errors below describe the path production dispatch takes under
    `use_pallas`. Roofline: per cached token a decode step reads K+V once, so
    bf16 moves 2*K*H*2 bytes/token while int8 moves 2*K*(H + 4) (payload +
    fp32 scale stripe) — a 2H/(H+4) HBM-traffic ratio that also equals the
    pool-capacity ratio the engine auto-sizer realizes."""
    B, N, K, H, bs, nb = 4, 8, 2, 64, 16, 16    # nb 16 -> split-K engaged
    num_blocks = nb * B + 2
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, 1, N, H), jnp.float32)
    kf = jax.random.normal(ks[1], (num_blocks, bs, K, H), jnp.float32)
    vf = jax.random.normal(ks[2], (num_blocks, bs, K, H), jnp.float32)
    bt = np.zeros((B, nb), np.int32)
    lens = np.zeros((B,), np.int32)
    rng = np.random.default_rng(0)
    perm = rng.permutation(np.arange(1, num_blocks))
    for b in range(B):
        lens[b] = int(rng.integers(bs, nb * bs))
        used = -(-int(lens[b]) // bs)
        bt[b, :used] = perm[b * nb:b * nb + used]
    bt, lens = jnp.asarray(bt), jnp.asarray(lens)

    def q8(x):
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-8) / 127.0
        return jnp.round(x / s[..., None]).astype(jnp.int8), \
            s.astype(jnp.float32)

    kp, ksc = q8(kf)
    vp, vsc = q8(vf)
    splits = pa_ops.default_num_splits(nb)
    bf16_tok_bytes = 2 * K * H * 2
    int8_tok_bytes = 2 * K * (H + 4)
    ratio = bf16_tok_bytes / int8_tok_bytes
    want = pa_ref.paged_attention_ref(q, kf, vf, bt, lens)
    want8 = pa_ref.paged_attention_ref(q, kp, vp, bt, lens,
                                       k_scale=ksc, v_scale=vsc)

    def bench(name, fn, derived):
        if quiet:
            return fn()
        return timed(name, fn, derived_fn=lambda _: derived)

    got = bench(
        f"kernels/paged_attention/bf16_b{B}_nb{nb}_splits{splits}",
        lambda: pa_ops.paged_decode_attention(
            q, kf, vf, bt, lens, num_splits=splits, interpret=True),
        f"hbm_bytes_per_tok={bf16_tok_bytes} "
        f"v5e_t_us={bf16_tok_bytes * int(jnp.sum(lens)) / TPU_V5E.hbm_bandwidth * 1e6:.3f}")
    got8 = bench(
        f"kernels/paged_attention/int8_b{B}_nb{nb}_splits{splits}",
        lambda: pa_ops.paged_decode_attention(
            q, kp, vp, bt, lens, k_scale=ksc, v_scale=vsc,
            num_splits=splits, interpret=True),
        f"hbm_bytes_per_tok={int8_tok_bytes} fused_dequant=in_vmem "
        f"speedup_mem_bound={ratio:.2f}x")
    err = float(jnp.max(jnp.abs(got - want)))
    err8 = float(jnp.max(jnp.abs(got8 - want8)))
    return {
        "num_splits": splits,
        "fused_path": True,          # paged_decode_attention IS the kernel
        "bf16": {"kv_bytes_per_token": bf16_tok_bytes},
        "int8": {"kv_bytes_per_token": int8_tok_bytes},
        "bytes_ratio": ratio,
        "parity_max_err_f32": err,
        "parity_max_err_int8": err8,
    }


def json_summary():
    """JSON-serializable summary (the CI perf-trajectory artifact schema).
    Interpret-mode wall time is meaningless on CPU, so the artifact carries
    only the deterministic roofline/parity numbers the gate can hold flat."""
    return {"paged_attention": paged_attention_bench(quiet=True)}


def run():
    key = jax.random.PRNGKey(0)
    # quant matmul: decode-shaped (M=batch rows, big K/N)
    M, K, N = 8, 1024, 1024
    x = jax.random.normal(key, (M, K), jnp.bfloat16)
    w = jax.random.normal(key, (K, N)) * 0.05
    for fmt in ("q8", "q4"):
        t = quantize(w, fmt)
        wbytes = t.nbytes()
        bf16_bytes = K * N * 2
        timed(f"kernels/quant_matmul/{fmt}_{M}x{K}x{N}",
              lambda: qm_ops.quant_matmul(x, t, interpret=True),
              derived_fn=lambda _: (
                  f"hbm_bytes={wbytes} vs bf16={bf16_bytes} "
                  f"speedup_mem_bound={bf16_bytes/wbytes:.2f}x "
                  f"v5e_t_us={wbytes/TPU_V5E.hbm_bandwidth*1e6:.2f}"))

    B, S, Nh, Kh, H = 1, 512, 4, 2, 64
    q = jax.random.normal(key, (B, S, Nh, H), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, Kh, H), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, Kh, H), jnp.bfloat16)
    flops = 4 * B * S * (S / 2) * Nh * H
    timed(f"kernels/flash_attention/causal_{S}",
          lambda: fa_ops.flash_attention(q, k, v, interpret=True),
          derived_fn=lambda _: (
              f"flops={flops:.2e} v5e_t_us={flops/TPU_V5E.peak_flops*1e6:.2f} "
              "o_s_memory=no_s2_materialization"))
    timed(f"kernels/flash_attention/window_{S}w128",
          lambda: fa_ops.flash_attention(q, k, v, window=128, interpret=True),
          derived_fn=lambda _: "block_skip=sub_quadratic_local_layers")

    Bs, Ss, Hh, P, G, Nst = 1, 512, 4, 64, 1, 64
    xs = jax.random.normal(key, (Bs, Ss, Hh, P))
    dt = jax.nn.softplus(jax.random.normal(key, (Bs, Ss, Hh)))
    A = -jnp.exp(jax.random.normal(key, (Hh,)) * 0.5)
    Bm = jax.random.normal(key, (Bs, Ss, G, Nst)) * 0.3
    Cm = jax.random.normal(key, (Bs, Ss, G, Nst)) * 0.3
    ssd_flops = Bs * Ss * Hh * (2 * 128 * Nst + 2 * 128 * P + 4 * Nst * P)
    timed(f"kernels/ssd/chunked_{Ss}",
          lambda: ssd_ops.ssd(xs, dt, A, Bm, Cm, interpret=True),
          derived_fn=lambda _: (
              f"flops={ssd_flops:.2e} "
              f"v5e_t_us={ssd_flops/TPU_V5E.peak_flops*1e6:.3f}"))

    paged_attention_bench()

    tools = jax.random.normal(key, (2048, 128))
    tools = tools / jnp.linalg.norm(tools, axis=-1, keepdims=True)
    qs = jax.random.normal(key, (4, 128))
    sim_bytes = 2048 * 128 * 4
    timed("kernels/topk_sim/2048x128",
          lambda: tk_ops.topk_tools(tools, qs, k=8, interpret=True),
          derived_fn=lambda _: (
              f"hbm_bytes={sim_bytes} (m x N sims never materialized) "
              f"v5e_t_us={sim_bytes/TPU_V5E.hbm_bandwidth*1e6:.3f}"))


# published Qwen2-7B Q8 products: (name, K, N, layers in the stack, the
# row counts the model multiplies it at). The LM head is one matrix; it
# gets two copies here, since a call that reads the same layer in every
# loop step does not depend on the loop and XLA hoists it out
Q8_SHAPES = (("k_v", 3584, 512, 28, (8, 1024)),
             ("q_o", 3584, 3584, 28, (8, 40, 1024)),
             ("gate_up", 3584, 18944, 28, (8, 40, 1024)),
             ("down", 18944, 3584, 28, (8, 40, 1024)),
             ("lm_head", 3584, 152064, 2, (8, 40)))
# (rows, route, (bm, bk, bn)); None picks `q8_tiles`, ("K", n) the whole K
# and the widest block of at most n columns. "sliced" hands the kernel a
# slice of the stack, as the layer scans did before the kernel read the
# stack itself, at the 2-D kernel's tiles
Q8_SWEEP = (
    (8, "sliced", (8, 512, 256)),
    (8, "stacked", None),
    (8, "stacked", ("K", 256)),
    (40, "stacked", None),
    (1024, "sliced", (128, 512, 256)),
    (1024, "stacked", (512, 512, 512)),
    (1024, "stacked", None),
)


def q8_chip_sweep(out_path, repeats: int = 10):
    """Time the compiled Q8 kernel on the chip: every published shape under
    every `Q8_SWEEP` setting, inside a loop over the stack's layers as a
    layer scan calls it. Reports the mean time a call, the weight-stream
    rate and the share of the v5e roofline (819 GB/s, 197 TFLOP/s bf16)."""
    import json
    from repro.kernels.quant_matmul.quant_matmul import _fit, q8_matmul

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"--chip needs a TPU; JAX sees {dev.platform!r}")
    rows = []
    for name, K, N, L, row_counts in Q8_SHAPES:
        kw, ks, kx = jax.random.split(jax.random.PRNGKey(K * 7 + N), 3)
        w = jax.jit(lambda k: jax.random.randint(
            k, (L, K, N), -127, 128, jnp.int8))(kw)
        scale = jax.random.uniform(ks, (L, 1, N), jnp.float32) * 1e-2
        for M, route, tiles in Q8_SWEEP:
            if M not in row_counts:
                continue
            if tiles is not None and tiles[0] == "K":
                tiles = (M, K, _fit(N, tiles[1]))
            x = jax.random.normal(kx, (M, K), jnp.bfloat16)

            # the weights are arguments: closed over, they would be
            # compiled into the program as constants
            @jax.jit
            def loop(x, w, scale, route=route, tiles=tiles):
                def body(i, tot):
                    l = i % L
                    if route == "sliced":
                        out = q8_matmul(x, w[l][None], scale[l][None], 0,
                                        tiles=tiles)
                    else:
                        out = q8_matmul(x, w, scale, l, tiles=tiles)
                    return tot + out[0, 0].astype(jnp.float32)
                return jax.lax.fori_loop(0, L * repeats, body, 0.0)

            label = f"kernels/q8/{name}_{M}x{K}x{N}/{route}/{tiles or 'auto'}"
            try:
                jax.block_until_ready(loop(x, w, scale))
            except Exception as e:                # a setting the chip refuses
                rows.append({"label": label, "error": str(e)[:300]})
                print(f"{label},refused,{str(e)[:120]!r}", flush=True)
                continue
            t0 = time.perf_counter()  # cc-lint: disable=CC001 -- real wall-clock is the measurement here
            for _ in range(3):
                jax.block_until_ready(loop(x, w, scale))
            us = (time.perf_counter() - t0) / (3 * L * repeats) * 1e6  # cc-lint: disable=CC001 -- real wall-clock is the measurement here
            nbytes = K * N + 4 * N + 2 * M * K + 2 * M * N
            flops = 2 * M * K * N
            least = max(nbytes / TPU_V5E.hbm_bandwidth,
                        flops / TPU_V5E.peak_flops)
            row = {"label": label, "us": us, "gb_s": nbytes / us / 1e3,
                   "roofline_pct": 100 * least / (us * 1e-6)}
            rows.append(row)
            emit(label, us, f"gb_s={row['gb_s']:.1f} "
                            f"roofline={row['roofline_pct']:.1f}%")
        del w
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps({"device": dev.device_kind, "rows": rows},
                                   indent=1))
    return rows


if __name__ == "__main__":
    import argparse
    from pathlib import Path
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chip", metavar="OUT",
                    help="time the compiled Q8 kernel on a TPU and write "
                         "its rows as JSON to OUT")
    args = ap.parse_args()
    if args.chip:
        q8_chip_sweep(Path(args.chip))
    else:
        run()
