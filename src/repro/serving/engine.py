"""Continuous-batching serving engine with a paged KV cache + prefix caching.

Slot model (vLLM-style at the granularity this framework needs): `max_batch`
decode slots; new requests prefill into free slots (prompts padded to a bucket
so jit reuse is bounded); every step() decodes all active slots in one batched
call. Completed rows free their slot immediately — no head-of-line blocking.

KV layouts:
  * "paged" (default for transformer-family models): KV lives in a block pool
    of `block_size`-token blocks; each slot maps logical positions to physical
    blocks through a block table. Blocks are refcounted (`BlockPool`) and the
    tool-description prompt prefixes that dominate CarbonCall's function-call
    workload are cached (`PrefixCache`): admission hashes the padded prompt at
    every block boundary, reuses already-prefilled blocks copy-on-write, and
    runs the model only over the non-cached suffix. Cache hits therefore skip
    real prefill compute AND are charged to `step_cost_fn` only for the
    suffix, so repeated tool prefixes show up as energy/carbon savings in the
    engine-backed week simulation. Decode reads go through the paged-attention
    kernel (Pallas on TPU for bf16 AND int8 pools — int8 via the fused-dequant
    variant; gather fallback on CPU, counted in `kernel_fallbacks`).
  * "dense": the original fixed (max_batch, max_seq) stripe — kept for
    non-transformer families and as the parity oracle for the paged path.

Admission is batched: one step admits up to *all* free slots through a single
padded prefill call (always padded to `max_batch` rows, so the jit cache holds
one executable per prompt/suffix bucket, not per admission count).
Decode/prefill executables are kept in per-variant caches so Q8<->Q4 hot
swaps reuse their compilations instead of retracing.

The engine is deliberately params-agnostic: `swap_params()` installs a new
weight tree (e.g. the Q4 variant) between steps, which is exactly the hot-swap
CarbonCall's TPS governor performs. Caches are untouched by a swap — both
variants share the same (paged or dense) cache layout (weight-only
quantization), so Q8 and Q4 serve from one block pool across hot swaps.
Prefix-cache *entries* are salted by variant, though: each variant's KV
projections differ, so a post-swap admission recomputes (and re-caches) its
prefix under the live weights instead of serving stale-variant KV/logits —
and swapping back re-hits the previous variant's still-resident entries.

Sharded execution: constructed with a `mesh` carrying a `data` axis, the
engine runs data-parallel — the decode batch (and the dense KV stripe's
batch dim) shards over the axis via NamedShardings resolved from the
standard logical-axis rules (`cache_batch -> data`), with constraints
re-anchored inside the jitted step so host-side slot bookkeeping between
steps never fights the layout. Dense layout only (the paged block pool's
host-side block tables are per-pod state); temperature-0 outputs are
token-identical to the unsharded engine. On CPU this is exercised under
`--xla_force_host_platform_device_count` (see tests/test_mesh_sharded.py
and benchmarks/fleet_scale.py). Jitted executables live in a process-wide
cache keyed by engine configuration, so a fleet of same-shape pods
compiles each program once instead of per pod.

Timebase: `clock` defaults to wall time, but tests and the engine-backed
carbon simulation inject a `VirtualClock` plus a `step_cost_fn`; each step
then advances virtual time by a deterministic, power-model-derived duration
instead of measuring the (meaningless on CPU) wall clock.

Session API: requests enter through a `Scheduler` (serving/scheduler.py) —
a priority waiting queue with deadlines — and callers hold `RequestHandle`s
(`poll()`/`result()`/`cancel()`). `EngineClient` is the facade several users
(e.g. a fleet pod's routed queries) share over ONE engine, so concurrent
sessions occupy decode slots together. Under paged block-pool pressure the
engine preempts the lowest-priority slot instead of reserving every slot's
worst-case decode growth up front: the victim's blocks are freed, its tokens
are saved, and it re-enters the queue; on resume the engine re-prefills the
saved sequence at its exact original positions (right-padded to a power-of-two
width, so causality makes the padding numerically invisible), which keeps
temperature-0 token streams identical to an unpreempted run.

Chunked prefill (`prefill_chunk=N`): a long prompt no longer monopolizes a
step. The queue head's prefill is split into N-token windows, one per engine
step, and `step()` alternates pending prefill work with a decode step for the
residents — so interactive decode streams keep emitting while a batch prompt
admits incrementally. A partial prefill is parked in the refcounted block
pool through the existing prefix-cache machinery (a half-prefilled chain IS a
cached prefix that the next chunk extends — the same block-handoff idiom
planned for prefill/decode disaggregation); the dense layout parks progress
in a reserved slot stripe instead. Each window resumes at its exact
positions, so temperature-0 streams are token-identical to an unchunked run.
Non-final windows are logged as kind "prefill_chunk" (0 tokens emitted); the
final window admits the request and is logged as a normal "prefill" row.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.config import ModelConfig, RuntimeConfig
from repro.kernels.paged_attention.ops import paged_attention_uses_fallback
from repro.models import get_model
from repro.models.transformer import paged_block_bytes
from repro.serving.block_pool import BlockPool, PrefixCache
from repro.serving.protocol import EngineConfig, EngineStats, SpecDecodeConfig
from repro.serving.sampler import sample_tokens
from repro.serving.scheduler import (
    CANCELLED, DONE, EngineStallError, PoolExhaustedError, RequestHandle,
    RUNNING, Scheduler, SessionRequest, TERMINAL, WAITING)
from repro.serving.tracing import StepTracer
from repro.sharding.param import ParamDef, init_params
from repro.sharding.rules import (SERVING_RULES, activate_mesh, activate_rules,
                                  logical_sharding)

# Process-wide executable cache. A fleet runs one engine per pod; pods with
# the same (cfg, rcfg, layout, batch, seq, mesh) would otherwise each pay
# their own jit compilation for identical programs — at 16-64 pods that
# dominates start-up. Cached values are jits of `_EngineExec` methods:
# `_EngineExec` holds only configuration-pure state (model = f(cfg), rcfg,
# dims, mesh shardings — all reflected in the cache key), never params or
# KV buffers, so the cache retains compiled programs, not engines. jax.jit's
# own signature cache still handles per-shape retraces (prompt buckets).
_SHARED_EXECS: Dict[tuple, Any] = {}


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 32
    eos_id: int = 1
    temperature: float = 0.0
    priority: int = 0                      # larger runs first / preempts lower
    deadline: Optional[float] = None       # absolute engine-clock wait limit
    tier: str = "default"                  # QoS class label (telemetry only)
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    status: str = WAITING
    submit_time: float = 0.0
    enqueue_time: float = 0.0
    queue_wait_s: float = 0.0              # total time spent WAITING (all stints)
    first_token_time: Optional[float] = None
    done_time: Optional[float] = None
    seq: int = -1                          # submission order (scheduler key)
    admit_seq: int = -1                    # admission order (victim tie-break)
    # saved token sequence (exact KV positions 0..len-1) while preempted
    resume_row: Optional[np.ndarray] = None
    # chunked-prefill progress while WAITING (cleared on admission/release):
    # the bucket-padded prompt row, how many positions are already prefilled,
    # and where that partial KV lives — a parked block chain (paged) or a
    # reserved slot stripe (dense)
    chunk_row: Optional[np.ndarray] = None
    chunk_done: int = 0
    chunk_blocks: List[int] = dataclasses.field(default_factory=list)
    chunk_cached: int = 0                  # real prompt tokens served from cache
    chunk_hit: bool = False
    chunk_slot: Optional[int] = None       # dense: reserved slot index


class VirtualClock:
    """Deterministic virtual time source for tests and carbon simulation.

    Only `advance()` moves time — reading it is free, so step durations are
    exactly what the injected `step_cost_fn` says they are.
    """

    def __init__(self, t0: float = 0.0):
        self.t = float(t0)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float):
        self.t += float(dt)


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _pow2(n: int, cap: int) -> int:
    """Round up to a power of two, capped — bounds jit executable counts for
    shapes derived from near-continuous quantities (suffix widths, prefix
    block counts, scatter index lengths)."""
    p = 1
    while p < n:
        p <<= 1
    return min(p, cap)


class _EngineExec:
    """Configuration-pure jit bodies for one engine shape.

    Holds ONLY what the jitted programs read — the model wrapper (a pure
    function of cfg), rcfg, dims, and the mesh shardings — never params,
    KV buffers or request state. `_SHARED_EXECS` caches jits of these
    methods across engines, so a fleet of same-shape pods shares compiled
    programs without the cache pinning whole engines in memory."""

    def __init__(self, model, rcfg: RuntimeConfig, max_seq: int,
                 block_size: int = 0, mesh=None, cache_shardings=None,
                 tok_sharding=None, len_sharding=None):
        self.model = model
        self.rcfg = rcfg
        self.max_seq = max_seq
        self.block_size = block_size
        self.mesh = mesh
        self.cache_shardings = cache_shardings
        self.tok_sharding = tok_sharding
        self.len_sharding = len_sharding

    def mesh_wrap(self, impl):
        """Trace the impl under the engine's mesh so model-internal
        `constrain` calls resolve against the serving rules."""
        if self.mesh is None:
            return impl

        def wrapped(*args):
            with activate_rules(SERVING_RULES), activate_mesh(self.mesh):
                return impl(*args)
        return wrapped

    def decode_impl(self, params, cache, tokens, lengths):
        if self.mesh is not None:
            # re-anchor the batch-sharded layout INSIDE the program: host-side
            # slot updates between steps can leave the cache committed to a
            # replicated layout, and a constraint (unlike jit in_shardings)
            # reshards instead of rejecting it
            cache = jax.tree.map(jax.lax.with_sharding_constraint, cache,
                                 self.cache_shardings)
            tokens = jax.lax.with_sharding_constraint(tokens,
                                                      self.tok_sharding)
            lengths = jax.lax.with_sharding_constraint(lengths,
                                                       self.len_sharding)
        logits, cache = self.model.decode_step(params, cache, tokens, lengths,
                                               self.rcfg)
        return logits, cache

    def decode_paged_impl(self, params, pool, tokens, lengths, block_tables):
        return self.model.decode_step_paged(params, pool, tokens, lengths,
                                            block_tables, self.rcfg,
                                            seq_cap=self.max_seq)

    def prefill_impl(self, params, batch):
        if self.mesh is not None:
            batch = {**batch, "tokens": jax.lax.with_sharding_constraint(
                batch["tokens"], self.tok_sharding)}
        B = batch["tokens"].shape[0]
        cache_spec = self.model.cache_spec(self.rcfg, B, self.max_seq)
        cache = init_params(cache_spec, jax.random.PRNGKey(0))
        return self.model.prefill(params, cache, batch, self.rcfg)

    def _gather_prefix(self, pool, prefix_bids):
        """Gather cached prefix blocks into a dense per-row (k, v) view."""
        nbp = prefix_bids.shape[1]

        def view(key):
            g = pool[key][:, prefix_bids]        # (L, B, nbp, bs, ...)
            return g.reshape(g.shape[0], g.shape[1], nbp * self.block_size,
                             *g.shape[4:])

        k_pre, v_pre = view("k"), view("v")
        if "k_scale" in pool:
            k_pre = (k_pre.astype(jnp.float32)
                     * view("k_scale")[..., None]).astype(jnp.bfloat16)
            v_pre = (v_pre.astype(jnp.float32)
                     * view("v_scale")[..., None]).astype(jnp.bfloat16)
        return k_pre, v_pre

    def prefill_prefix_impl(self, params, pool, batch, prefix_bids,
                            prefix_lens):
        """Gather the cached prefix blocks into a dense per-row view and run
        the suffix-only prefill against it."""
        k_pre, v_pre = self._gather_prefix(pool, prefix_bids)
        return self.model.prefill_paged(params, batch, k_pre, v_pre,
                                        prefix_lens, self.rcfg)

    def prefill_chunk_impl(self, params, pool, batch, prefix_bids,
                           prefix_lens, need_logits):
        """One chunked-prefill window against the parked block chain (the
        already-prefilled positions of the same prompt). `need_logits` is
        static: middle windows skip the unembed entirely."""
        k_pre, v_pre = self._gather_prefix(pool, prefix_bids)
        return self.model.prefill_chunk(params, batch, k_pre, v_pre,
                                        prefix_lens, self.rcfg,
                                        need_logits=need_logits)

    def prefill_dense_chunk_impl(self, params, cache, batch, prefix_lens,
                                 p_len, need_logits):
        """One chunked-prefill window against a dense slot stripe: the
        already-prefilled positions live in `cache[:, :, :p_len]` (`p_len`
        static, pow2-rounded by the caller to bound executable counts)."""
        k_pre = cache["k"][:, :, :p_len]
        v_pre = cache["v"][:, :, :p_len]
        if "k_scale" in cache:
            k_pre = (k_pre.astype(jnp.float32)
                     * cache["k_scale"][:, :, :p_len][..., None]
                     ).astype(jnp.bfloat16)
            v_pre = (v_pre.astype(jnp.float32)
                     * cache["v_scale"][:, :, :p_len][..., None]
                     ).astype(jnp.bfloat16)
        return self.model.prefill_chunk(params, batch, k_pre, v_pre,
                                        prefix_lens, self.rcfg,
                                        need_logits=need_logits)

    def verify_impl(self, params, pool, batch, prefix_bids, prefix_lens):
        """Speculative-decode verify: gather each row's canonical prefix
        (including a partially filled last block — `prefix_lens` masks the
        tail) and run one batched forward over the k+1 candidate window
        positions. Reads the pool, never writes it: the engine commits the
        returned window KV for the accepted positions only."""
        k_pre, v_pre = self._gather_prefix(pool, prefix_bids)
        return self.model.verify_paged(params, batch, k_pre, v_pre,
                                       prefix_lens, self.rcfg)

    def scatter_impl(self, pool, entry, dst, src_b, src_s):
        """Write entry[key][:, src_b[i], src_s[i]] into flat pool position
        dst[i] (= block_id * block_size + offset) for every i, per leaf."""
        out = {}
        for key, leaf in pool.items():
            nb, bs = leaf.shape[1], leaf.shape[2]
            flat = leaf.reshape(leaf.shape[0], nb * bs, *leaf.shape[3:])
            vals = entry[key][:, src_b, src_s].astype(leaf.dtype)
            out[key] = flat.at[:, dst].set(vals).reshape(leaf.shape)
        return out

    def scatter_kv_impl(self, pool, k, v, dst, src_b, src_s):
        from repro.models.transformer import quantize_kv_for_cache
        entry = quantize_kv_for_cache("k_scale" in pool, k, v)
        return self.scatter_impl(pool, entry, dst, src_b, src_s)

    def copy_block_impl(self, pool, dst, src):
        return {key: leaf.at[:, dst].set(leaf[:, src])
                for key, leaf in pool.items()}


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, rcfg: RuntimeConfig, *,
                 config: Optional[EngineConfig] = None,
                 max_batch: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 prompt_buckets=None,
                 kv_layout: Optional[str] = None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 spec_decode: Optional[SpecDecodeConfig] = None,
                 mesh=None,
                 clock: Callable[[], float] = time.monotonic,
                 step_cost_fn: Optional[Callable[[str, int, int], float]] = None):
        # sizing comes from ONE serializable EngineConfig (the control
        # protocol's construction payload); the explicit kwargs remain as
        # per-field overrides so existing call sites read unchanged. None
        # means "no override" — EngineConfig's own defaults match the
        # pre-protocol keyword defaults exactly.
        base = config if config is not None else EngineConfig()
        over = {k: v for k, v in (("max_batch", max_batch),
                                  ("max_seq", max_seq),
                                  ("kv_layout", kv_layout),
                                  ("block_size", block_size),
                                  ("num_blocks", num_blocks),
                                  ("prefill_chunk", prefill_chunk),
                                  ("spec_decode", spec_decode))
                if v is not None}
        if prompt_buckets is not None:
            over["prompt_buckets"] = tuple(prompt_buckets)
        self.config = base.replace(**over) if over else base
        config = self.config
        # kv_cache_dtype: the serializable config and the runtime config both
        # carry it (the model layer reads rcfg). Merge rule: an explicit int8
        # on EITHER surface wins — rcfg-driven call sites predate the config
        # field and must keep working — and both end up agreeing, so the
        # engine's wire snapshot always states the pool dtype truthfully.
        if config.kv_cache_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"unknown kv_cache_dtype {config.kv_cache_dtype!r}; "
                "expected 'bf16' or 'int8'")
        kv_dtype = config.kv_cache_dtype
        if kv_dtype == "bf16" and rcfg.kv_cache_dtype != "bf16":
            kv_dtype = rcfg.kv_cache_dtype
        if kv_dtype != rcfg.kv_cache_dtype:
            rcfg = dataclasses.replace(rcfg, kv_cache_dtype=kv_dtype)
        if kv_dtype != config.kv_cache_dtype:
            self.config = config = config.replace(kv_cache_dtype=kv_dtype)
        max_batch = config.max_batch
        max_seq = config.max_seq
        prompt_buckets = config.prompt_buckets
        kv_layout = config.kv_layout
        block_size = config.block_size
        num_blocks = config.num_blocks
        prefill_chunk = config.prefill_chunk
        self.cfg = cfg
        self.rcfg = rcfg
        self.model = get_model(cfg)
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        # data-parallel sharded execution: with a mesh carrying a `data` axis
        # the decode batch (and the dense KV stripe's batch dim) is sharded
        # over it via NamedShardings resolved from the standard logical-axis
        # rules — the multi-host scale-out path, exercisable on CPU under
        # --xla_force_host_platform_device_count. Dense layout only: the
        # paged block pool's host-side block tables are per-pod state.
        self.mesh = mesh
        self.data_shards = 1
        if mesh is not None:
            if "data" not in mesh.shape:
                raise ValueError("sharded engine needs a mesh with a 'data' "
                                 f"axis; got axes {tuple(mesh.shape)}")
            if kv_layout not in ("auto", "dense"):
                raise ValueError(
                    f"kv_layout={kv_layout!r} under a mesh: the paged block "
                    "pool is single-device per pod, so the sharded engine "
                    "path requires 'dense' (or 'auto', which picks it)")
            kv_layout = "dense"
            if cfg.family in ("whisper", "vlm"):
                raise ValueError(f"family {cfg.family!r} does not support the "
                                 "sharded engine path")
            self.data_shards = int(mesh.shape["data"])
            if max_batch % self.data_shards != 0:
                raise ValueError(
                    f"max_batch={max_batch} must divide over the data axis "
                    f"({self.data_shards} shards)")
        # always include a terminal bucket of max_seq: max_seq <= the smallest
        # configured bucket used to leave an empty tuple (IndexError at
        # admission), and prompts longer than the largest bucket were silently
        # over-truncated to it instead of to the full context window
        self.prompt_buckets = tuple(sorted(
            {b for b in prompt_buckets if b < max_seq} | {max_seq}))
        self.clock = clock
        # step_cost_fn(kind, tokens, active) -> seconds; with a VirtualClock it
        # sets the measured duration of each step (kind "prefill" passes the
        # prompt tokens actually computed this step — prefix-cache hits are
        # excluded, so cached tool prefixes cost ~0 virtual time/energy —
        # "decode" passes tokens emitted this step).
        self.step_cost_fn = step_cost_fn
        self.variant_name = "bf16"
        self.swap_count = 0

        if kv_layout not in ("auto", "paged", "dense"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}; expected "
                             "'auto', 'paged' or 'dense'")
        if kv_layout == "auto":
            kv_layout = "paged" if self.model.supports_paged() else "dense"
        if kv_layout == "paged" and not self.model.supports_paged():
            raise ValueError(f"{cfg.name}: family {cfg.family!r} does not "
                             "implement the paged KV contract")
        self.kv_layout = kv_layout
        if kv_layout == "paged":
            self.block_size = block_size
            self.blocks_per_slot = -(-max_seq // block_size)
            if num_blocks is None:
                # all slots full + one transient CoW block per slot + one
                # slot's worth of slack for cached prefixes + scratch block 0
                num_blocks = ((max_batch + 1) * self.blocks_per_slot
                              + max_batch + 2)
                if rcfg.kv_cache_dtype == "int8":
                    # same byte budget as the bf16 default pool, ~2x the
                    # blocks: int8 halves the k/v leaves, the fp32 scale
                    # stripes claw a little back (ratio 2H/(H+4))
                    budget = (num_blocks - 1) * paged_block_bytes(
                        cfg, block_size, "bf16")
                    num_blocks = 1 + budget // paged_block_bytes(
                        cfg, block_size, "int8")
            pool_spec = self.model.paged_cache_spec(rcfg, num_blocks,
                                                    block_size)
            self.pool = init_params(pool_spec, jax.random.PRNGKey(0))
            self.block_pool = BlockPool(num_blocks, block_size)
            self.prefix_cache = PrefixCache(self.block_pool)
            self.block_tables = np.zeros((max_batch, self.blocks_per_slot),
                                         np.int32)
            self.slot_blocks: List[List[int]] = [[] for _ in range(max_batch)]
            self.lengths = np.zeros((max_batch,), np.int32)
            self.cache = None
            self.cow_count = 0
        else:
            cache_spec = self.model.cache_spec(rcfg, max_batch, max_seq)
            self.cache = init_params(cache_spec, jax.random.PRNGKey(0))
            self.lengths = jnp.zeros((max_batch,), jnp.int32)
        # chunked prefill: split a long prompt's admission into
        # `prefill_chunk`-token windows, one per step, interleaved with
        # decode steps for the residents (None = monolithic prefill)
        if prefill_chunk is not None:
            if mesh is not None:
                raise ValueError(
                    "prefill_chunk: chunk progress is per-pod host-side "
                    "state, unsupported on the sharded engine path")
            if prefill_chunk <= 0:
                raise ValueError(
                    f"prefill_chunk must be positive, got {prefill_chunk}")
            if not self.model.supports_paged():
                raise ValueError(
                    f"{cfg.name}: family {cfg.family!r} does not implement "
                    "the chunked prefill contract (pattern-1 transformer "
                    "families only)")
            if self.kv_layout == "paged":
                # block-aligned windows keep parked chains on block
                # boundaries, so partial inserts reuse the prefix cache's
                # chunk_lens keying unchanged
                prefill_chunk = -(-prefill_chunk // block_size) * block_size
        self.prefill_chunk = prefill_chunk
        # speculative decoding over the variant ladder: a cheap draft
        # variant proposes k tokens per step, the resident variant verifies
        # them in one batched forward. Draft KV lives in leased scratch
        # blocks — the canonical per-slot block tables only ever hold
        # verify-variant KV. Draft params arrive via `set_draft_params`
        # (the executor wires its pre-quantized variant tree in); until
        # then — and whenever k == 0 — steps take the plain decode path.
        sd = self.config.spec_decode
        if sd is not None:
            if self.kv_layout != "paged":
                raise ValueError(
                    "spec_decode requires the paged KV layout: draft KV is "
                    "staged in leased pool blocks")
            if sd.k < 0 or any(x < 0 for x in sd.k_ladder):
                raise ValueError("spec_decode: draft lengths must be >= 0")
        self.spec_k = sd.k if sd is not None else 0
        self.draft_params = None
        self.draft_variant = sd.draft_variant if sd is not None else ""
        self.draft_tokens = 0            # drafted this engine's lifetime
        self.accepted_tokens = 0         # drafts that entered an output
        self._spec_leases: List[List[int]] = [[] for _ in range(max_batch)]
        self._prefer_prefill = True      # alternation flag: prefill <-> decode
        self._chunk_slots: set = set()   # dense: slots reserved by parked chunks
        self.slots: List[Optional[Request]] = [None] * max_batch
        # the admitted token row + emitted-count baseline per slot: together
        # they reconstruct the exact KV sequence when a slot is preempted
        self._slot_row: List[Optional[np.ndarray]] = [None] * max_batch
        self._slot_emit0 = [0] * max_batch
        self.scheduler = Scheduler()
        self._admit_seq = 0
        self._rid_counter = 0
        self.key = jax.random.PRNGKey(42)

        # sharded-path placement: NamedShardings resolved from the standard
        # logical-axis rules (cache_batch -> data)
        cache_shardings = tok_sharding = len_sharding = None
        if self.mesh is not None:
            cspec = self.model.cache_spec(rcfg, max_batch, max_seq)
            cache_shardings = jax.tree.map(
                lambda d: logical_sharding(d.logical, d.shape, self.mesh,
                                           SERVING_RULES),
                cspec, is_leaf=lambda x: isinstance(x, ParamDef))
            tok_sharding = NamedSharding(self.mesh,
                                         PartitionSpec("data", None))
            len_sharding = NamedSharding(self.mesh, PartitionSpec("data"))
        self._exec = _EngineExec(
            self.model, rcfg, max_seq,
            block_size=getattr(self, "block_size", 0), mesh=self.mesh,
            cache_shardings=cache_shardings, tok_sharding=tok_sharding,
            len_sharding=len_sharding)
        # per-variant executable caches: a hot swap flips the param tree
        # structure (bf16 arrays vs QTensor nodes), so each variant gets its
        # own jitted decode/prefill and swapping back reuses the compilation.
        # The per-engine dicts front the process-wide _SHARED_EXECS cache so
        # same-shape fleet pods compile once.
        self._decode_fns: Dict[str, Any] = {}
        self._verify_fns: Dict[str, Any] = {}
        self._prefill_fns: Dict[str, Any] = {}
        self._prefill_prefix_fns: Dict[str, Any] = {}
        self._prefill_chunk_fns: Dict[str, Any] = {}
        self._dense_chunk_fns: Dict[str, Any] = {}
        self._scatter_cache_fn = self._shared_exec(
            "scatter_cache",
            lambda: jax.jit(self._exec.scatter_impl, donate_argnums=(0,)))
        self._scatter_kv_fn = self._shared_exec(
            "scatter_kv",
            lambda: jax.jit(self._exec.scatter_kv_impl, donate_argnums=(0,)))
        self._copy_block_fn = self._shared_exec(
            "copy_block",
            lambda: jax.jit(self._exec.copy_block_impl, donate_argnums=(0,)))
        # telemetry
        self.tokens_emitted = 0
        self.prefill_tokens_total = 0
        self.prefill_tokens_saved = 0
        self.peak_active = 0               # max concurrent resident sessions
        # paged decode steps that ran the gather reference instead of the
        # Pallas kernel; the dispatch decision is a pure function of rcfg,
        # so it is computed once and counted per step
        self._paged_fallback = (self.kv_layout == "paged"
                                and paged_attention_uses_fallback(rcfg))
        self.kernel_fallbacks = 0
        self.step_log: List[Dict] = []
        # profiler spans and each step's `host`/`compiles` record
        self._tracer = StepTracer(clock)
        self._phase = self._tracer.phase

    def _exec_key(self, kind: str, *extra) -> tuple:
        """Process-wide executable identity: everything the jitted impls read
        off `self._exec` is either in this key or a pure function of it."""
        return (self.cfg, self.rcfg, self.kv_layout, self.max_batch,
                self.max_seq, getattr(self, "block_size", 0), self.mesh,
                kind) + extra

    def _shared_exec(self, kind: str, build, *extra):
        key = self._exec_key(kind, *extra)
        fn = _SHARED_EXECS.get(key)
        if fn is None:
            fn = _SHARED_EXECS[key] = build()
        return fn

    def _decode_fn(self, variant: Optional[str] = None):
        """Jitted decode step for `variant` (default: the resident variant).
        Speculative drafting passes the draft variant explicitly — the
        per-variant cache already exists for hot swaps, so draft executables
        ride the same mechanism."""
        variant = variant or self.variant_name
        fn = self._decode_fns.get(variant)
        if fn is None:
            impl = (self._exec.decode_paged_impl if self.kv_layout == "paged"
                    else self._exec.decode_impl)

            def build():
                return jax.jit(self._exec.mesh_wrap(impl),
                               donate_argnums=(1,))
            fn = self._shared_exec("decode", build, variant)
            self._decode_fns[variant] = fn
        return fn

    def _verify_fn(self):
        fn = self._verify_fns.get(self.variant_name)
        if fn is None:
            fn = self._shared_exec(
                "verify", lambda: jax.jit(self._exec.verify_impl),
                self.variant_name)
            self._verify_fns[self.variant_name] = fn
        return fn

    def _prefill_fn(self):
        fn = self._prefill_fns.get(self.variant_name)
        if fn is None:
            def build():
                return jax.jit(self._exec.mesh_wrap(self._exec.prefill_impl))
            fn = self._shared_exec("prefill", build, self.variant_name)
            self._prefill_fns[self.variant_name] = fn
        return fn

    def _prefill_prefix_fn(self):
        fn = self._prefill_prefix_fns.get(self.variant_name)
        if fn is None:
            fn = self._shared_exec(
                "prefill_prefix",
                lambda: jax.jit(self._exec.prefill_prefix_impl),
                self.variant_name)
            self._prefill_prefix_fns[self.variant_name] = fn
        return fn

    def _prefill_chunk_fn(self):
        fn = self._prefill_chunk_fns.get(self.variant_name)
        if fn is None:
            fn = self._shared_exec(
                "prefill_chunk",
                lambda: jax.jit(self._exec.prefill_chunk_impl,
                                static_argnums=(5,)),
                self.variant_name)
            self._prefill_chunk_fns[self.variant_name] = fn
        return fn

    def _dense_chunk_fn(self):
        fn = self._dense_chunk_fns.get(self.variant_name)
        if fn is None:
            fn = self._shared_exec(
                "prefill_dense_chunk",
                lambda: jax.jit(self._exec.prefill_dense_chunk_impl,
                                static_argnums=(4, 5)),
                self.variant_name)
            self._dense_chunk_fns[self.variant_name] = fn
        return fn

    # -- public API ---------------------------------------------------------

    def swap_params(self, params, variant_name: str):
        """Hot-swap the weight tree (CarbonCall Q8<->Q4 switch)."""
        self.params = params
        self.variant_name = variant_name
        self.swap_count += 1
        # drop parked partial prefills: their KV was computed under the old
        # weights, and restarting under the live variant keeps every admitted
        # prefill single-variant (the parity guarantee chunking preserves)
        for req in self.scheduler.waiting:
            if req.chunk_row is not None:
                self._release_chunk(req)
        # a swap landing mid-draft (tests drive the lease helpers directly;
        # step() itself is atomic) abandons the in-flight draft: scratch
        # leases go back to the pool, the next step re-drafts under
        # whatever the ladder now pairs
        if self.kv_layout == "paged":
            for i in range(self.max_batch):
                self._spec_release_leases(i)

    def set_draft_params(self, params, variant_name: str):
        """Install the draft variant's weight tree (normally the executor's
        pre-quantized Q4 tree). Spec steps stay disabled until this is set,
        and fall back to plain decode whenever the draft and resident
        variants coincide (e.g. after a governor swap *to* Q4)."""
        if self.config.spec_decode is None:
            raise ValueError(
                "set_draft_params: engine was built without spec_decode")
        self.draft_params = params
        self.draft_variant = variant_name

    def set_draft_k(self, k: int):
        """Set the draft length (the governor's carbon-modulated knob);
        k = 0 degrades to plain decode."""
        if k < 0:
            raise ValueError(f"set_draft_k: k must be >= 0, got {k}")
        self.spec_k = int(k)

    def submit(self, req: Request) -> RequestHandle:
        """Queue a request; returns an async handle (poll/result/cancel)."""
        self.scheduler.enqueue(req, self.clock())
        return RequestHandle(self, req)

    def client(self) -> "EngineClient":
        """A submission facade onto this (possibly shared) engine."""
        return EngineClient(self)

    def next_rid(self) -> int:
        self._rid_counter += 1
        return self._rid_counter - 1

    def cancel(self, req: Request) -> bool:
        """Cancel a waiting or running request, freeing its slot and blocks.
        False if it already reached a terminal state."""
        if req.status in TERMINAL:
            return False
        if req.status == WAITING:
            self.scheduler.remove(req)
            self._release_chunk(req)
        elif req in self.slots:
            self._free_slot(self.slots.index(req))
        req.status = CANCELLED
        req.resume_row = None
        self.scheduler.note_cancelled(req)
        return True

    @property
    def pending(self) -> List[Request]:
        """Waiting requests in admission (priority) order."""
        return self.scheduler.waiting

    @property
    def active(self) -> int:
        return sum(s is not None for s in self.slots)

    def has_work(self) -> bool:
        return self.active > 0 or self.scheduler.has_waiting()

    def scheduler_stats(self) -> Dict[str, float]:
        """Scheduler counters plus the engine's slot-occupancy high-water
        mark (`peak_active` >= 2 means cross-request batched decode)."""
        stats = self.scheduler.stats()
        stats["peak_active"] = self.peak_active
        return stats

    def prefix_cache_stats(self) -> Dict[str, int]:
        if self.kv_layout != "paged":
            return {}
        return {"hits": self.prefix_cache.hits,
                "misses": self.prefix_cache.misses,
                "entries": len(self.prefix_cache.entries),
                "cow": self.cow_count,
                "free_blocks": self.block_pool.num_free,
                "prefill_tokens_total": self.prefill_tokens_total,
                "prefill_tokens_saved": self.prefill_tokens_saved}

    def stats(self) -> EngineStats:
        """The versioned telemetry snapshot (protocol.EngineStats): one
        schema unifying `scheduler_stats()` + `prefix_cache_stats()` plus
        swap/token counters — what a worker publishes over the wire and
        what the JSON benchmark artifacts persist."""
        return EngineStats.from_engine(self)

    def step(self) -> List[Request]:
        """Admit waiting requests into free slots (one batched prefill, one
        preemption-resume re-prefill, or — with `prefill_chunk` — one prefill
        window) or run one batched decode step. With chunking enabled the
        step alternates pending prefill work with a decode step for the
        residents, so a long prompt admits incrementally instead of stalling
        every resident stream at once. Returns requests completed this step.

        Each step is one `engine.step` profiler span, and its `step_log`
        entry carries `host` (seconds per phase) and `compiles`
        (serving/tracing.py)."""
        if not self.has_work():
            return []
        n = len(self.step_log)
        self._tracer.begin()
        try:
            return self._step()
        finally:
            self._tracer.end(self.step_log[n] if len(self.step_log) > n
                             else None, n)

    def _step(self) -> List[Request]:
        t0 = self.clock()
        # who was resident when the step started: prefill-kind steps stall
        # exactly these streams, and the executor charges them the step's
        # dt/energy share (see EngineExecutor._attribute_steps)
        resident_rids = [s.rid for s in self.slots if s is not None]
        completed: List[Request] = []
        work: Optional[Dict] = None
        spec: Optional[Dict] = None
        with self._phase("admit"):
            for req in self.scheduler.expire_due(t0):
                self._release_chunk(req)
            if self.prefill_chunk is None or self._prefer_prefill \
                    or not self.active:
                work = self._prefill_work()
            if work is None and not self.active \
                    and self.prefill_chunk is not None:
                # liveness fallback: the head is blocked (e.g. its final
                # chunk needs a slot another parked dense chunk reserves)
                # and nothing can decode — advance the first parked chunk so
                # reserved slots drain. A bounded priority inversion, traded
                # for progress.
                head = self.scheduler.head()
                for req in self.scheduler.waiting:
                    if req is not head and req.chunk_row is not None:
                        work = self._chunk_step(req, self._free_slots())
                        if work is not None:
                            break
        if work is not None:
            kind = work["kind"]
            tokens_this_step = work["tokens"]
            charged, cached = work["charged"], work["cached"]
            rids = work["rids"]
            occupancy = max(self.active, 1)      # includes any new slots
            self._prefer_prefill = False
        elif self.active:
            charged = cached = 0
            # speculative step when armed; None falls back to plain decode
            # (pool pressure, a row too near max_seq) — spec is purely
            # opportunistic, never preempts, and degrades to today's path
            spec = self._spec_step(completed) if self._spec_ready() else None
            if spec is not None:
                tokens_this_step, rids = spec["tokens"], spec["rids"]
                kind = "spec_verify"
            else:
                tokens_this_step, rids = self._decode_active(completed)
                kind = "decode"
            occupancy = max(len(rids), 1)        # before completions free slots
            self._prefer_prefill = True
            if self._paged_fallback:
                # this step's paged-attention reads (decode, or spec draft
                # rounds + verify) ran the gather reference, not the kernel
                self.kernel_fallbacks += 1
        else:
            if self.scheduler.has_waiting():
                raise PoolExhaustedError(
                    "paged KV pool exhausted: cannot admit any pending "
                    "request with an idle engine — raise num_blocks",
                    waiting=len(self.pending),
                    free_blocks=(self.block_pool.num_free
                                 if self.kv_layout == "paged" else 0))
            return completed
        self.peak_active = max(self.peak_active, self.active, occupancy)
        if self.step_cost_fn is not None and hasattr(self.clock, "advance"):
            # cost basis is the *computed* prompt work: the full requested
            # prompt size (no free truncation discount vs the analytic
            # backend) minus tokens served from the prefix cache; a resume
            # is charged its full re-prefilled sequence (preemption is not
            # free, which is exactly why the scheduler only uses it under
            # real pool pressure)
            if kind == "spec_verify":
                # acceptance-aware pricing: the k draft rounds are charged
                # at the draft variant's power point, the single batched
                # verify at the resident variant's (see
                # EngineExecutor._step_cost)
                cost = (float(self.step_cost_fn(
                            "spec_draft", spec["drafted"], occupancy))
                        + float(self.step_cost_fn(
                            "spec_verify", spec["verified"], occupancy)))
            else:
                cost_tokens = charged if kind != "decode" else tokens_this_step
                cost = float(self.step_cost_fn(kind, cost_tokens, occupancy))
            if cost > 0.0:
                self.clock.advance(cost)
        with self._phase("emit"):
            for req in completed:            # completion is at end of step
                req.done_time = self.clock()
                self.scheduler.note_done(req, req.done_time)
            self.tokens_emitted += tokens_this_step
            rec = {
                "kind": kind, "tokens": tokens_this_step,
                "variant": self.variant_name,
                "active": occupancy, "prompt_tokens": charged,
                "cached_tokens": cached, "rids": rids,
                "resident_rids": resident_rids,
            }
            if spec is not None:
                # spec rows emit per-rid token *counts* — consumers that
                # assume one token per rid per decode row (invariants, soak
                # oracles) expand `emitted` instead
                rec["drafted"] = spec["drafted"]
                rec["accepted"] = spec["accepted"]
                rec["emitted"] = spec["emitted"]
            self.step_log.append(rec)
        # read after every phase has closed, so the phases sum to at most dt
        rec["dt"] = max(self.clock() - t0, 1e-9)
        return completed

    def run_until_drained(self, max_steps: int = 100000) -> List[Request]:
        done = []
        for _ in range(max_steps):
            if not self.has_work():
                return done
            done.extend(self.step())
        if self.has_work():
            raise EngineStallError(
                f"engine not drained after {max_steps} steps "
                f"(active={self.active}, waiting={len(self.pending)})")
        return done

    # -- admission ----------------------------------------------------------

    def _free_slots(self) -> List[int]:
        """Slots available for fresh admission — excludes slots a parked
        dense chunk has reserved for its in-progress stripe."""
        return [i for i, s in enumerate(self.slots)
                if s is None and i not in self._chunk_slots]

    def _prefill_work(self) -> Optional[Dict]:
        """One unit of pending prefill work for the queue head — a resume
        re-prefill, a chunk window, or a batched fresh admission. Returns the
        step-log record for it, or None when nothing can run (the step
        decodes instead)."""
        head = self.scheduler.head()
        if head is None:
            return None
        free = self._free_slots()
        if head.resume_row is not None:
            # strict priority: a blocked resume never lets lower-priority
            # fresh admissions jump it — decode continues instead
            if not free:
                return None
            got = self._try_resume(head, free[0])
            if got < 0:
                return None
            # a resume re-prefills already-emitted context, samples nothing
            return {"kind": "prefill", "tokens": 0, "charged": got,
                    "cached": 0, "rids": [head.rid]}
        if self._chunk_needed(head):
            return self._chunk_step(head, free)
        if not free:
            return None
        admitted, charged, cached = self._admit_batch(free)
        if not admitted:
            return None
        return {"kind": "prefill", "tokens": len(admitted),
                "charged": charged, "cached": cached,
                "rids": [r.rid for r in admitted]}

    def _chunk_needed(self, req: Request) -> bool:
        """Whether `req` admits through the chunked path: chunking enabled,
        and the prompt's *non-cached* prefill work exceeds one window."""
        if self.prefill_chunk is None or req.resume_row is not None:
            return False
        if req.chunk_row is not None:
            return True                  # mid-chunk: must finish via chunks
        b = _bucket(len(req.prompt), self.prompt_buckets)
        if b <= self.prefill_chunk:
            return False
        if self.kv_layout != "paged":
            return True
        row = self._padded_row(req.prompt, b)
        hit = self.prefix_cache.lookup(row, salt=self.variant_name)
        cached = hit.cached_len if hit else 0
        if cached >= b:
            return False                 # whole-row hit: one cheap admission
        return b - cached > self.prefill_chunk

    def _chunk_step(self, req: Request, free: List[int]) -> Optional[Dict]:
        if self.kv_layout == "paged":
            return self._chunk_step_paged(req, free)
        return self._chunk_step_dense(req, free)

    def _admit_batch(self, free: List[int]):
        """Batched admission: fill free slots this step. Returns
        (admitted requests, prompt tokens charged, prompt tokens cached)."""
        if self.kv_layout == "paged":
            return self._admit_batch_paged(free)
        reqs: List[Request] = []
        for req in self.scheduler.waiting:
            if self._chunk_needed(req):
                break       # chunked admissions run one window per step
            reqs.append(req)
            if len(reqs) == len(free):
                break
        if not reqs:
            return [], 0, 0
        now = self.clock()
        for req in reqs:
            self.scheduler.note_admitted(req, now)
        b = _bucket(max(len(r.prompt) for r in reqs), self.prompt_buckets)
        with self._phase("inputs"):
            toks = np.zeros((self.max_batch, b), np.int32)
            for i, r in enumerate(reqs):
                toks[i] = self._padded_row(r.prompt, b)
            batch = self._prefill_batch(toks)
        with self._phase("launch"):
            logits, cache_n, lengths_n = self._prefill_fn()(self.params,
                                                            batch)
        with self._phase("fetch"):
            lengths_n = np.asarray(lengths_n)
        for i, (req, slot) in enumerate(zip(reqs, free)):
            with self._phase("launch"):
                self.cache = jax.tree.map(
                    lambda c, p: c.at[:, slot].set(p[:, i].astype(c.dtype))
                    if c.ndim >= 2 else c, self.cache, cache_n)
                self.lengths = self.lengths.at[slot].set(int(lengths_n[i]))
            self._place(req, slot, toks[i])
            tok = self._sample(logits[i:i + 1], req)
            with self._phase("emit"):
                self._emit(req, slot, int(tok[0]))
                self._slot_emit0[slot] = len(req.output)
        return reqs, sum(len(r.prompt) for r in reqs), 0

    def _place(self, req: Request, slot: int, row: np.ndarray):
        """Common slot bookkeeping at (re)admission."""
        self.slots[slot] = req
        self._slot_row[slot] = np.asarray(row, np.int32)
        req.status = RUNNING
        req.admit_seq = self._admit_seq
        self._admit_seq += 1

    def _admit_batch_paged(self, free: List[int]):
        """Paged admission: look up each prompt's longest cached prefix chain,
        share those blocks (copy-on-write protected), allocate fresh blocks
        for the rest, and prefill only the non-cached suffixes.

        Block accounting is watermark-based: an admission needs its fresh
        prompt blocks plus one near-term growth block per resident slot —
        NOT the old worst-case decode-growth reserve. Over-commitment is
        resolved later by preemption (see `_decode_alloc`), so slots admit
        far more eagerly. The queue head may preempt strictly-lower-priority
        running slots to get in; deeper queue entries only take what is
        freely available and otherwise stay queued."""
        bs = self.block_size
        cand: List[Request] = []
        for req in self.scheduler.waiting:
            if req.resume_row is not None or self._chunk_needed(req):
                break               # resumes and chunked prefills are
                                    # re-admitted/advanced one per step
            cand.append(req)
            if len(cand) == len(free):
                break
        if not cand:
            return [], 0, 0
        b = _bucket(max(len(r.prompt) for r in cand), self.prompt_buckets)
        nb_prompt = -(-b // bs)
        rows = []          # admission records
        for pos, req in enumerate(cand):
            row = self._padded_row(req.prompt, b)
            hit = self.prefix_cache.lookup(row, salt=self.variant_name)
            cached_len = hit.cached_len if hit else 0
            cached_blocks = list(hit.blocks) if hit else []
            if hit and cached_len == b and hit.last_logits is None:
                # whole-row match against an interior boundary of a longer
                # cached row: no last-position logits stored, so keep the
                # final stripe out of the chain and recompute it (which also
                # upgrades the entry with logits for future full hits)
                cached_len -= bs if b % bs == 0 else b % bs
                cached_blocks = cached_blocks[:-1]
            # hold refs on the cached chain BEFORE allocating: eviction under
            # pressure must not free blocks this admission is about to share
            for bid in cached_blocks:
                self.block_pool.incref(bid)
            n_fresh = nb_prompt - len(cached_blocks)
            headroom = self.active + len(rows) + 1
            preempted_before = self.scheduler.preemptions
            ok = self._reclaim(n_fresh + headroom,
                               priority=req.priority if pos == 0 else None)
            fresh = self._alloc_blocks(n_fresh) if ok else None
            if fresh is None:
                for bid in cached_blocks:
                    self.block_pool.decref(bid)
                break
            self.scheduler.note_admitted(req, self.clock())
            rows.append({"req": req, "row": row, "hit": hit,
                         "cached_len": cached_len,
                         "blocks": cached_blocks + fresh})
            # hit/miss accounting only for *completed* admissions — a
            # deferred request retries its lookup on every later step
            if cached_len > 0:
                self.prefix_cache.hits += 1
            else:
                self.prefix_cache.misses += 1
            if self.scheduler.preemptions > preempted_before:
                # the head preempted a victim to get in: stop the batch here
                # so the requeued victim (front of its priority class) is
                # reconsidered before lower-priority fresh candidates grab
                # its freed blocks — no same-step priority inversion
                break
        if not rows:
            return [], 0, 0

        full = [r for r in rows if r["cached_len"] == b]
        compute = [r for r in rows if r["cached_len"] < b]
        if compute:
            if all(r["cached_len"] == 0 for r in compute):
                logits_c = self._prefill_cold(compute, b)
            else:
                logits_c = self._prefill_suffix(compute, b)
            for i, r in enumerate(compute):
                with self._phase("fetch"):
                    r["logits"] = np.asarray(logits_c[i])
                with self._phase("emit"):
                    self.prefix_cache.insert(r["row"], r["blocks"],
                                             last_logits=r["logits"],
                                             salt=self.variant_name)
        for r in full:
            r["logits"] = r["hit"].last_logits

        charged = cached = 0
        for r, slot in zip(rows, free):
            req = r["req"]
            pad = b - min(len(req.prompt), b)
            cached_real = max(0, r["cached_len"] - pad)
            charged += max(0, len(req.prompt) - cached_real)
            cached += cached_real
            self.slot_blocks[slot] = list(r["blocks"])
            self.block_tables[slot] = 0
            self.block_tables[slot, :len(r["blocks"])] = r["blocks"]
            self.lengths[slot] = b
            self._place(req, slot, r["row"])
            tok = self._sample(r["logits"][None, :], req)
            with self._phase("emit"):
                self._emit(req, slot, int(tok[0]))
                self._slot_emit0[slot] = len(req.output)
        self.prefill_tokens_total += charged + cached
        self.prefill_tokens_saved += cached
        return [r["req"] for r in rows], charged, cached

    # -- chunked prefill -----------------------------------------------------

    def _chunk_init(self, req: Request):
        """First window of a chunked prefill: bucket the prompt and (paged)
        adopt the longest cached prefix chain — the request holds one ref per
        block, exactly like an admission, so eviction cannot free the chain
        while it is being extended."""
        b = _bucket(len(req.prompt), self.prompt_buckets)
        row = self._padded_row(req.prompt, b)
        cached_len = 0
        if self.kv_layout == "paged":
            hit = self.prefix_cache.lookup(row, salt=self.variant_name)
            if hit is not None:
                cached_len = hit.cached_len
                for bid in hit.blocks:
                    self.block_pool.incref(bid)
                req.chunk_blocks = list(hit.blocks)
        pad = b - min(len(req.prompt), b)
        req.chunk_row = row
        req.chunk_done = cached_len
        req.chunk_cached = max(0, cached_len - pad)
        req.chunk_hit = cached_len > 0

    def _chunk_window(self, req: Request, start: int, end: int,
                      final: bool):
        """Run one prefill window [start, end) for the parked chain (paged).
        Returns last-position logits (only meaningful when `final`)."""
        bs = self.block_size
        row = req.chunk_row
        b = len(row)
        nwin = end - start
        if start == 0:
            # cold first window: nothing parked to attend — reuse the stock
            # full prefill over a right-padded pow2 row (causality makes the
            # padding invisible) and scatter positions [0, end). Never final:
            # `_chunk_needed` guarantees the first window cannot cover the
            # whole bucket, so no logits are needed here.
            W = _pow2(end, self.max_seq)
            with self._phase("inputs"):
                toks = np.zeros((self.max_batch, W), np.int32)
                toks[0, :end] = row[:end]
                batch = self._prefill_batch(toks)
            with self._phase("launch"):
                _, cache_n, _ = self._prefill_fn()(self.params, batch)
            with self._phase("inputs"):
                dst = [req.chunk_blocks[p // bs] * bs + p % bs
                       for p in range(end)]
                idx = self._scatter_idx(dst, [0] * end, list(range(end)))
            with self._phase("launch"):
                self.pool = self._scatter_cache_fn(self.pool, cache_n, *idx)
            return None
        # middle/final window: the parked chain is the "cached prefix", the
        # window is a left-padded suffix at its exact absolute positions —
        # the same shape as a prefix-cache-hit admission, so the rounding
        # tricks (pow2 window width / prefix block count) carry over and the
        # result is bit-identical to the same positions inside one
        # monolithic prefill
        W = _pow2(nwin, b)
        nbp = _pow2(-(-start // bs), self.blocks_per_slot)
        with self._phase("inputs"):
            toks = np.zeros((self.max_batch, W), np.int32)
            toks[0, W - nwin:] = row[start:end]
            bids = np.zeros((self.max_batch, nbp), np.int32)
            bids[0, :start // bs] = req.chunk_blocks[:start // bs]
            plens = np.zeros((self.max_batch,), np.int32)
            plens[0] = start
            batch = self._prefill_batch(toks)
            batch["positions"] = jnp.arange(end - W, end, dtype=jnp.int32)
            bids, plens = jnp.asarray(bids), jnp.asarray(plens)
        with self._phase("launch"):
            logits, (k_win, v_win) = self._prefill_chunk_fn()(
                self.params, self.pool, batch, bids, plens, final)
        with self._phase("inputs"):
            dst = [req.chunk_blocks[p // bs] * bs + p % bs
                   for p in range(start, end)]
            src_s = [p - (end - W) for p in range(start, end)]
            idx = self._scatter_idx(dst, [0] * nwin, src_s)
        with self._phase("launch"):
            self.pool = self._scatter_kv_fn(self.pool, k_win, v_win, *idx)
        return logits

    def _chunk_step_paged(self, req: Request,
                          free: List[int]) -> Optional[Dict]:
        bs = self.block_size
        if req.chunk_row is None:
            self._chunk_init(req)
        row = req.chunk_row
        b = len(row)
        start = req.chunk_done
        end = min(start + self.prefill_chunk, b)
        final = end >= b
        if final and not free:
            return None                  # the final window needs a slot
        need = -(-end // bs) - len(req.chunk_blocks)
        if need > 0:
            if not self._reclaim(need + self.active + 1,
                                 priority=req.priority, exclude=req):
                return None              # parked state persists; retry later
            fresh = self._alloc_blocks(need)
            if fresh is None:            # unreachable after _reclaim
                return None
            req.chunk_blocks.extend(fresh)
        logits = self._chunk_window(req, start, end, final)
        req.chunk_done = end
        pad = b - min(len(req.prompt), b)
        charged = max(0, end - max(start, pad))
        self.prefill_tokens_total += charged
        if not final:
            # park the progress as ordinary prefix-cache entries: pinned by
            # the request's refs while it extends them, CoW-shareable by
            # concurrent admissions of the same prefix, and plain evictable
            # cache if the chunk is dropped
            with self._phase("emit"):
                self.prefix_cache.insert(row[:end], req.chunk_blocks,
                                         salt=self.variant_name)
            self.scheduler.note_chunk_step(req)
            return {"kind": "prefill_chunk", "tokens": 0, "charged": charged,
                    "cached": 0, "rids": [req.rid]}
        # final window: admit into the slot exactly like a batched admission
        charged += max(0, len(req.prompt) - b)   # no free truncation discount
        slot = free[0]
        self.scheduler.note_admitted(req, self.clock())
        with self._phase("fetch"):
            logits = np.asarray(logits)
        with self._phase("emit"):
            self.prefix_cache.insert(row, req.chunk_blocks,
                                     last_logits=logits[0],
                                     salt=self.variant_name)
        if req.chunk_hit:
            self.prefix_cache.hits += 1
        else:
            self.prefix_cache.misses += 1
        cached = req.chunk_cached
        self.prefill_tokens_total += cached
        self.prefill_tokens_saved += cached
        self.slot_blocks[slot] = list(req.chunk_blocks)   # refs transfer
        self.block_tables[slot] = 0
        self.block_tables[slot, :len(req.chunk_blocks)] = req.chunk_blocks
        self.lengths[slot] = b
        self._place(req, slot, row)
        tok = self._sample(logits[0:1], req)
        with self._phase("emit"):
            self._emit(req, slot, int(tok[0]))
            self._slot_emit0[slot] = len(req.output)
        self._clear_chunk(req)
        return {"kind": "prefill", "tokens": 1, "charged": charged,
                "cached": cached, "rids": [req.rid]}

    def _chunk_step_dense(self, req: Request,
                          free: List[int]) -> Optional[Dict]:
        if req.chunk_row is None:
            if not free:
                return None              # needs a slot stripe to reserve
            self._chunk_init(req)
            req.chunk_slot = free[0]
            self._chunk_slots.add(free[0])
        slot = req.chunk_slot
        row = req.chunk_row
        b = len(row)
        start = req.chunk_done
        end = min(start + self.prefill_chunk, b)
        final = end >= b
        nwin = end - start
        logits = None
        if start == 0:
            # cold first window (never final, see _chunk_window): stock full
            # prefill of [0, end), window copied into the reserved stripe
            W = _pow2(end, self.max_seq)
            with self._phase("inputs"):
                toks = np.zeros((self.max_batch, W), np.int32)
                toks[0, :end] = row[:end]
                batch = self._prefill_batch(toks)
            with self._phase("launch"):
                _, cache_n, _ = self._prefill_fn()(self.params, batch)
                self.cache = jax.tree.map(
                    lambda c, p: c.at[:, slot, :end].set(
                        p[:, 0, :end].astype(c.dtype)) if c.ndim >= 3 else c,
                    self.cache, cache_n)
        else:
            from repro.models.transformer import quantize_kv_for_cache
            p_len = _pow2(start, self.max_seq)
            W = _pow2(nwin, b)
            # the prefix view is cache[:, :, :p_len] — batch rows align with
            # cache slots, so the window MUST ride in row `slot` to attend the
            # reserved stripe (row 0 would read slot 0's resident KV instead)
            with self._phase("inputs"):
                toks = np.zeros((self.max_batch, W), np.int32)
                toks[slot, W - nwin:] = row[start:end]
                plens = np.zeros((self.max_batch,), np.int32)
                plens[slot] = start
                batch = self._prefill_batch(toks)
                batch["positions"] = jnp.arange(end - W, end, dtype=jnp.int32)
                plens = jnp.asarray(plens)
            with self._phase("launch"):
                logits, (k_win, v_win) = self._dense_chunk_fn()(
                    self.params, self.cache, batch, plens, p_len, final)
                entry = quantize_kv_for_cache("k_scale" in self.cache,
                                              k_win, v_win)
                for key, val in entry.items():
                    self.cache[key] = self.cache[key].at[
                        :, slot, start:end].set(
                            val[:, slot, W - nwin:].astype(
                                self.cache[key].dtype))
        req.chunk_done = end
        # advance the stripe's fill mark: an interleaved dense decode step
        # blindly writes its per-row KV at lengths[slot] for EVERY row, so
        # pointing it at the next window's first position makes the garbage
        # write land where the next chunk overwrites it
        with self._phase("launch"):
            self.lengths = self.lengths.at[slot].set(end)
        pad = b - min(len(req.prompt), b)
        charged = max(0, end - max(start, pad))
        if not final:
            self.scheduler.note_chunk_step(req)
            return {"kind": "prefill_chunk", "tokens": 0, "charged": charged,
                    "cached": 0, "rids": [req.rid]}
        charged += max(0, len(req.prompt) - b)   # no free truncation discount
        self.scheduler.note_admitted(req, self.clock())
        self._chunk_slots.discard(slot)
        self._place(req, slot, row)
        with self._phase("fetch"):
            logits = np.asarray(logits)
        tok = self._sample(logits[slot:slot + 1], req)
        with self._phase("emit"):
            self._emit(req, slot, int(tok[0]))
            self._slot_emit0[slot] = len(req.output)
        self._clear_chunk(req)
        return {"kind": "prefill", "tokens": 1, "charged": charged,
                "cached": 0, "rids": [req.rid]}

    def _clear_chunk(self, req: Request):
        req.chunk_row = None
        req.chunk_done = 0
        req.chunk_blocks = []
        req.chunk_cached = 0
        req.chunk_hit = False
        req.chunk_slot = None

    def _release_chunk(self, req: Request):
        """Drop a parked partial prefill (cancel / expiry / hot swap / pool
        pressure). Paged: the request's block refs are dropped — progress
        survives as ordinary prefix-cache entries until eviction actually
        needs the blocks, so a quick retry often resumes for free. Dense:
        the reserved slot stripe is returned."""
        if req.chunk_row is None:
            return
        if self.kv_layout == "paged":
            for bid in req.chunk_blocks:
                self.block_pool.decref(bid)
        elif req.chunk_slot is not None:
            self._chunk_slots.discard(req.chunk_slot)
            self.lengths = self.lengths.at[req.chunk_slot].set(0)
        self._clear_chunk(req)
        self.scheduler.note_chunk_dropped(req)

    # -- preemption / resume -------------------------------------------------

    def _reclaim(self, want_free: int, *, priority: Optional[int],
                 exclude: Optional[Request] = None) -> bool:
        """Bring the pool's free count up to `want_free`: first by LRU
        prefix-cache eviction, then by dropping another waiting request's
        parked partial prefill (its chain becomes evictable cache entries),
        then (when `priority` is given) by preempting strictly-lower-priority
        running slots on the caller's behalf. `exclude` protects the caller's
        own parked chain while it extends it."""
        while self.block_pool.num_free < want_free:
            if self.prefix_cache.evict_lru():
                continue
            if self._drop_parked_chunk(exclude):
                continue
            victim = None
            if priority is not None:
                victim = Scheduler.pick_victim(
                    [(s, r) for s, r in enumerate(self.slots)
                     if r is not None], below=priority)
            if victim is None:
                return False
            self._preempt_slot(victim)
        return True

    def _drop_parked_chunk(self, exclude: Optional[Request]) -> bool:
        """Release the lowest-priority (newest on ties) parked partial
        prefill to relieve block pressure. The dropped request stays queued:
        its progress survives as ordinary prefix-cache entries until eviction
        actually needs the blocks, so a quick retry often resumes for free."""
        if self.kv_layout != "paged":
            return False
        cands = [r for r in self.scheduler.waiting
                 if r.chunk_row is not None and r is not exclude]
        if not cands:
            return False
        self._release_chunk(min(cands, key=lambda r: (r.priority, -r.seq)))
        return True

    def _preempt_slot(self, i: int):
        """Evict slot `i`: save the exact token sequence its KV covers
        (admitted row + tokens emitted since, truncated at the saturation
        cap), free its blocks, and put it back at the front of its priority
        class. Temperature-0 streams resume token-identically."""
        req = self.slots[i]
        e = self._slot_emit0[i]
        seq = np.concatenate([
            self._slot_row[i],
            np.asarray(req.output[e - 1:len(req.output) - 1], np.int32)])
        req.resume_row = seq[:int(self.lengths[i])]
        self._free_slot(i)
        self.scheduler.note_preempted(req)
        self.scheduler.requeue(req, self.clock())

    def _try_resume(self, req: Request, slot: int) -> int:
        """Re-admit a preempted request: allocate blocks for its saved
        sequence and re-prefill it at the exact original positions. The row
        is right-padded to a power-of-two width — causal attention never sees
        the padding, so the restored KV is bit-identical to what the slot
        held at preemption. Returns the recomputed token count (the step's
        charged prefill work), or -1 if blocks are still unavailable."""
        bs = self.block_size
        row = req.resume_row
        L = len(row)
        nb = -(-L // bs)
        if not self._reclaim(nb + self.active + 1, priority=req.priority):
            return -1
        blocks = self._alloc_blocks(nb)
        if blocks is None:                   # unreachable after _reclaim
            return -1
        W = _pow2(L, self.max_seq)
        with self._phase("inputs"):
            toks = np.zeros((self.max_batch, W), np.int32)
            toks[0, :L] = row
            batch = self._prefill_batch(toks)
        with self._phase("launch"):
            _, cache_n, _ = self._prefill_fn()(self.params, batch)
        with self._phase("inputs"):
            dst = [blocks[p // bs] * bs + p % bs for p in range(L)]
            idx = self._scatter_idx(dst, [0] * L, list(range(L)))
        with self._phase("launch"):
            self.pool = self._scatter_cache_fn(self.pool, cache_n, *idx)
        self.slot_blocks[slot] = list(blocks)
        self.block_tables[slot] = 0
        self.block_tables[slot, :nb] = blocks
        self.lengths[slot] = L
        self._place(req, slot, row)
        self._slot_emit0[slot] = len(req.output)
        req.resume_row = None
        self.scheduler.note_admitted(req, self.clock())
        return L

    def _decode_alloc(self, i: int) -> Optional[int]:
        """Allocate one block for decoding slot `i` under pool pressure:
        evict cached prefixes, then preempt the lowest-priority slot (most
        recently admitted on ties). Returns None when slot `i` preempted
        *itself* (its decode is skipped this step); raises only when a single
        resident sequence genuinely cannot fit the pool."""
        while True:
            bid = self.block_pool.alloc()
            if bid is not None:
                return bid
            if self.prefix_cache.evict_lru():
                continue
            if self._drop_parked_chunk(None):
                continue                 # parked chains yield before slots do
            active = [(s, r) for s, r in enumerate(self.slots)
                      if r is not None]
            if len(active) <= 1:
                raise PoolExhaustedError(
                    "paged KV pool exhausted mid-decode with no preemptable "
                    "slot — raise num_blocks",
                    waiting=len(self.pending),
                    free_blocks=self.block_pool.num_free)
            victim = Scheduler.pick_victim(active)
            self._preempt_slot(victim)
            if victim == i:
                return None

    def _prefill_cold(self, compute, b: int):
        """No cached prefix anywhere in the batch: run the stock full-row
        prefill and scatter every position into the rows' blocks."""
        with self._phase("inputs"):
            toks = np.zeros((self.max_batch, b), np.int32)
            for i, r in enumerate(compute):
                toks[i] = r["row"]
            batch = self._prefill_batch(toks)
        with self._phase("launch"):
            logits, cache_n, _ = self._prefill_fn()(self.params, batch)
        with self._phase("inputs"):
            dst, src_b, src_s = [], [], []
            for i, r in enumerate(compute):
                for p in range(b):
                    dst.append(r["blocks"][p // self.block_size]
                               * self.block_size + p % self.block_size)
                    src_b.append(i)
                    src_s.append(p)
            idx = self._scatter_idx(dst, src_b, src_s)
        with self._phase("launch"):
            self.pool = self._scatter_cache_fn(self.pool, cache_n, *idx)
        return logits

    def _prefill_suffix(self, compute, b: int):
        """At least one row has a cached prefix: gather the prefix KV views
        and run the model over the suffixes only. The suffix width and the
        prefix-view block count are rounded up to powers of two (capped at
        the bucket / slot capacity) so the executable cache stays
        O(log^2 max_seq) per variant instead of one entry per cached-length
        combination — the extra columns are fully masked, so rounding is
        numerically free."""
        bs = self.block_size
        s_suf = _pow2(b - min(r["cached_len"] for r in compute), b)
        p_len = max(r["cached_len"] for r in compute)
        nbp = _pow2(-(-p_len // bs), self.blocks_per_slot)
        with self._phase("inputs"):
            toks = np.zeros((self.max_batch, s_suf), np.int32)
            bids = np.zeros((self.max_batch, nbp), np.int32)
            plens = np.zeros((self.max_batch,), np.int32)
            for i, r in enumerate(compute):
                cl = r["cached_len"]
                suf = r["row"][cl:]
                toks[i, s_suf - len(suf):] = suf
                bids[i, :cl // bs] = r["blocks"][:cl // bs]
                plens[i] = cl
            batch = self._prefill_batch(toks)
            batch["positions"] = jnp.arange(b - s_suf, b, dtype=jnp.int32)
            bids, plens = jnp.asarray(bids), jnp.asarray(plens)
        with self._phase("launch"):
            logits, (k_suf, v_suf) = self._prefill_prefix_fn()(
                self.params, self.pool, batch, bids, plens)
        with self._phase("inputs"):
            dst, src_b, src_s = [], [], []
            for i, r in enumerate(compute):
                for p in range(r["cached_len"], b):
                    dst.append(r["blocks"][p // bs] * bs + p % bs)
                    src_b.append(i)
                    src_s.append(p - (b - s_suf))
            idx = self._scatter_idx(dst, src_b, src_s)
        with self._phase("launch"):
            self.pool = self._scatter_kv_fn(self.pool, k_suf, v_suf, *idx)
        return logits

    @staticmethod
    def _scatter_idx(dst, src_b, src_s):
        """Pad scatter index vectors to a power-of-two length so the jitted
        scatter executables stay O(log) in count rather than one per
        cached-length combination; pad entries write row 0 position 0 into
        flat slot 0 — inside the reserved scratch block, never read back."""
        pad = _pow2(max(len(dst), 1), 1 << 62) - len(dst)
        return (jnp.asarray(dst + [0] * pad, jnp.int32),
                jnp.asarray(src_b + [0] * pad, jnp.int32),
                jnp.asarray(src_s + [0] * pad, jnp.int32))

    def _padded_row(self, prompt: List[int], b: int) -> np.ndarray:
        p = prompt[-b:] if len(prompt) > b else \
            [0] * (b - len(prompt)) + list(prompt)
        return np.asarray(p, np.int32)

    def _alloc_blocks(self, n: int) -> Optional[List[int]]:
        """Allocate n blocks, evicting LRU prefix-cache entries under
        pressure; None (nothing held) if the pool is truly exhausted."""
        got: List[int] = []
        while len(got) < n:
            bid = self.block_pool.alloc()
            if bid is not None:
                got.append(bid)
            elif not self.prefix_cache.evict_lru():
                for g in got:
                    self.block_pool.decref(g)
                return None
        return got

    def _prefill_batch(self, tokens):
        batch = {"tokens": jnp.asarray(tokens)}
        if self.cfg.family == "whisper":
            batch["frames"] = jnp.zeros(
                (tokens.shape[0], self.cfg.num_audio_frames, self.cfg.d_model),
                jnp.bfloat16)
        if self.cfg.family == "vlm":
            B, S = tokens.shape
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, None, :], (3, B, S))
        return batch

    # -- decode -------------------------------------------------------------

    def _decode_active(self, completed: List[Request]):
        """One batched decode step over the resident slots. Returns
        (tokens emitted, rids of the slots that actually decoded — block
        pressure may preempt slots out of the step)."""
        with self._phase("inputs"):
            last = np.zeros((self.max_batch, 1), np.int32)
            for i, req in enumerate(self.slots):
                if req is not None:
                    last[i, 0] = req.output[-1] if req.output else (
                        req.prompt[-1] if req.prompt else 0)
        if self.kv_layout == "paged":
            with self._phase("blocks"):
                self._prepare_decode_blocks()
            with self._phase("inputs"):
                # `lengths` is copied: it changes in place below, while the
                # device may still read the uploaded array (zero-copy on
                # the CPU)
                args = (jnp.asarray(last), jnp.asarray(self.lengths.copy()),
                        jnp.asarray(self.block_tables))
            with self._phase("launch"):
                logits, self.pool = self._decode_fn()(self.params, self.pool,
                                                      *args)
            # saturate at max_seq: a full context drops further KV writes
            # cleanly (decode keeps attending the intact prompt) instead of
            # stepping back and overwriting the last real position
            for i, req in enumerate(self.slots):
                if req is not None:
                    self.lengths[i] = min(self.lengths[i] + 1, self.max_seq)
        else:
            with self._phase("inputs"):
                last = jnp.asarray(last)
            with self._phase("launch"):
                logits, self.cache = self._decode_fn()(
                    self.params, self.cache, last, self.lengths)
                self.lengths = jnp.where(
                    jnp.asarray([s is not None for s in self.slots]),
                    jnp.minimum(self.lengths + 1, self.max_seq), self.lengths)
        live = [(i, req) for i, req in enumerate(self.slots)
                if req is not None]
        if live:
            toks = self._sample(logits, live[0][1])
        emitted = 0
        rids: List[int] = []
        with self._phase("emit"):
            for i, req in live:
                tok = int(toks[i])
                self._emit(req, i, tok)
                emitted += 1
                rids.append(req.rid)
                if tok == req.eos_id or len(req.output) >= req.max_new_tokens:
                    completed.append(req)    # done_time stamped at end of step
                    req.status = DONE
                    self._free_slot(i)
        return emitted, rids

    def _prepare_decode_blocks(self):
        """Host-side block management before a paged decode step: extend a
        slot's chain when its write position crosses a block boundary, and
        copy-on-write when it is about to write into a shared block (a cached
        prefix whose last block is partially filled — divergence point).
        Allocation failures preempt the lowest-priority slot instead of
        crashing — the scheduling answer to removing the admission-time
        decode-growth reserve."""
        bs = self.block_size
        for i, req in enumerate(self.slots):
            if req is None or self.slots[i] is None:
                continue                     # slot preempted earlier this step
            pos = int(self.lengths[i])
            if pos >= self.max_seq:
                continue                     # write is dropped by the model
            blk = pos // bs
            bid = int(self.block_tables[i, blk])
            if bid == 0:
                new = self._decode_alloc(i)
                if new is None:
                    continue                 # slot i preempted itself
                self.block_tables[i, blk] = new
                self.slot_blocks[i].append(new)
            elif self.block_pool.is_shared(bid):
                new = self._decode_alloc(i)
                if new is None:
                    continue
                with self._phase("launch"):
                    self.pool = self._copy_block_fn(self.pool, new, bid)
                self.block_pool.decref(bid)
                self.block_tables[i, blk] = new
                self.slot_blocks[i][blk] = new
                self.cow_count += 1

    # -- speculative decoding ------------------------------------------------

    def _spec_ready(self) -> bool:
        """Whether this step may draft: spec configured, draft weights
        installed, k > 0, the ladder actually has two rungs resident (a
        governor swap *to* the draft variant collapses to plain decode),
        and every resident stream is greedy — temperature-0 acceptance is
        what makes spec byte-identical to plain decode."""
        if (self.config.spec_decode is None or self.kv_layout != "paged"
                or self.spec_k <= 0 or self.draft_params is None
                or self.draft_variant == self.variant_name):
            return False
        return all(r is None or r.temperature <= 0.0 for r in self.slots)

    def _spec_reserve(self, n: int) -> bool:
        """Ensure >= n free blocks using prefix-cache eviction only — spec
        steps are opportunistic: they never preempt a slot or drop a parked
        chunk, they just fall back to plain decode."""
        while self.block_pool.num_free < n:
            if not self.prefix_cache.evict_lru():
                return False
        return True

    def _spec_acquire_leases(self, i: int, L: int, k: int) -> List[int]:
        """Lease scratch blocks covering draft positions [L, L+k-1] for slot
        `i`. When L sits mid-block the first lease starts as a copy of the
        canonical partial block, so drafts read real prefix KV below L; the
        canonical block itself is never written by the draft path."""
        bs = self.block_size
        blocks = [self.block_pool.alloc()
                  for _ in range(L // bs, (L + k - 1) // bs + 1)]
        assert all(b is not None for b in blocks), \
            "spec lease alloc failed despite reservation"
        self._spec_leases[i] = blocks
        if L % bs:
            src = int(self.block_tables[i, L // bs])
            if src:                      # always true for a live slot
                with self._phase("launch"):
                    self.pool = self._copy_block_fn(self.pool, blocks[0],
                                                    src)
        return blocks

    def _spec_release_leases(self, i: int):
        """Return slot `i`'s draft scratch blocks to the pool (rejected-draft
        reconciliation; also the cancel/expiry/hot-swap abandon path)."""
        for bid in self._spec_leases[i]:
            self.block_pool.decref(bid)
        self._spec_leases[i] = []

    def _spec_step(self, completed: List[Request]) -> Optional[Dict]:
        """One speculative decode step over the resident slots: k greedy
        draft tokens under the draft variant (KV staged in leased scratch
        blocks), one batched verify forward under the resident variant over
        each row's k+1 candidate window, then accept the longest agreeing
        prefix plus the verify token — at temperature 0 that stream is
        byte-identical to plain decode, draft quality only moves the
        acceptance rate. Returns the step record, or None to fall back to a
        plain decode step (pool pressure, or a row within k+1 of max_seq:
        context-edge saturation stays the plain path's semantics).

        Block accounting is exact: worst-case need is counted and reserved
        before anything is allocated, leases are released in full right
        after the accepted window KV is scattered into the canonical chain,
        and the canonical tables advance by each row's accepted length via
        the same alloc/CoW rules as `_prepare_decode_blocks`."""
        bs, k = self.block_size, self.spec_k
        live = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        with self._phase("blocks"):
            need = 0
            for i, _ in live:
                L = int(self.lengths[i])
                if L + k + 1 > self.max_seq:
                    return None
                # leases span blocks L//bs .. (L+k-1)//bs; the canonical
                # chain may need one block per boundary crossed by writes at
                # [L, L+k] plus an alloc/CoW for the write block itself
                need += (L + k - 1) // bs - L // bs + 1
                need += (L + k) // bs - L // bs
                bid = int(self.block_tables[i, L // bs])
                if bid == 0 or self.block_pool.is_shared(bid):
                    need += 1
            if not self._spec_reserve(need):
                return None

        # -- draft: k greedy rounds under the draft variant ------------------
        with self._phase("inputs"):
            last0 = np.zeros((self.max_batch, 1), np.int32)
            for i, r in live:
                last0[i, 0] = r.output[-1] if r.output else (
                    r.prompt[-1] if r.prompt else 0)
        with self._phase("blocks"):
            draft_tables = self.block_tables.copy()
            for i, _ in live:
                L = int(self.lengths[i])
                for j, bid in enumerate(self._spec_acquire_leases(i, L, k)):
                    draft_tables[i, L // bs + j] = bid
        with self._phase("inputs"):
            draft_lengths = self.lengths.copy()
            draft_toks = np.zeros((self.max_batch, k), np.int32)
            cur = last0.copy()
            dfn = self._decode_fn(self.draft_variant)
            tables_j = jnp.asarray(draft_tables)
        for j in range(k):
            with self._phase("inputs"):
                args = (jnp.asarray(cur), jnp.asarray(draft_lengths),
                        tables_j)
            with self._phase("launch"):
                logits, self.pool = dfn(self.draft_params, self.pool, *args)
            # raw argmax == sample_tokens at temperature 0, without
            # splitting self.key — parity with the plain path's key
            # evolution is irrelevant under greedy decoding (enforced by
            # _spec_ready)
            with self._phase("sample"):
                nxt = jnp.argmax(logits, axis=-1)
            with self._phase("fetch"):
                nxt = np.asarray(nxt, np.int32)
            for i, _ in live:
                draft_toks[i, j] = nxt[i]
                cur[i, 0] = nxt[i]
                draft_lengths[i] += 1

        # -- verify: one batched forward over the k+1 windows ----------------
        W = k + 1
        nbp = _pow2(max(-(-int(self.lengths[i]) // bs) for i, _ in live),
                    self.blocks_per_slot)
        with self._phase("inputs"):
            toks = np.zeros((self.max_batch, W), np.int32)
            poss = np.zeros((self.max_batch, W), np.int32)
            bids = np.zeros((self.max_batch, nbp), np.int32)
            plens = np.zeros((self.max_batch,), np.int32)
            for i, _ in live:
                L = int(self.lengths[i])
                toks[i, 0] = last0[i, 0]
                toks[i, 1:] = draft_toks[i]
                poss[i] = np.arange(L, L + W)
                nb = -(-L // bs)
                bids[i, :nb] = self.block_tables[i, :nb]
                plens[i] = L
            batch = self._prefill_batch(toks)
            batch["positions"] = jnp.asarray(poss)
            bids, plens = jnp.asarray(bids), jnp.asarray(plens)
        with self._phase("launch"):
            logits, (k_win, v_win) = self._verify_fn()(
                self.params, self.pool, batch, bids, plens)
        with self._phase("sample"):
            greedy = jnp.argmax(logits, axis=-1)
        with self._phase("fetch"):
            greedy = np.asarray(greedy, np.int32)                # (B, W)

        # -- accept, commit canonical KV, reconcile leases -------------------
        drafted = k * len(live)
        accepted = 0
        outs: List[List[int]] = []
        dst: List[int] = []
        src_b: List[int] = []
        src_s: List[int] = []
        for i, r in live:
            L = int(self.lengths[i])
            a = 0
            while a < k and draft_toks[i, a] == greedy[i, a]:
                a += 1
            toks_out: List[int] = []
            for j in range(a + 1):
                t = int(greedy[i, j])
                toks_out.append(t)
                if (t == r.eos_id
                        or len(r.output) + len(toks_out)
                        >= r.max_new_tokens):
                    break
            e = len(toks_out)
            accepted += min(e, a)        # the e-th token is the free verify
            outs.append(toks_out)
            # window position m holds the token whose KV belongs at L+m:
            # m=0 is the pre-step last token, m>=1 the accepted drafts. The
            # last emitted token's KV is NOT written — exactly the plain
            # decode invariant, so preemption-resume reconstruction and
            # lengths bookkeeping stay unchanged.
            for p in range(L, L + e):
                blk = p // bs
                bid = int(self.block_tables[i, blk])
                if bid == 0:
                    new = self.block_pool.alloc()
                    assert new is not None, "spec commit alloc underflowed"
                    self.block_tables[i, blk] = new
                    self.slot_blocks[i].append(new)
                    bid = new
                elif self.block_pool.is_shared(bid):
                    new = self.block_pool.alloc()
                    assert new is not None, "spec CoW alloc underflowed"
                    with self._phase("launch"):
                        self.pool = self._copy_block_fn(self.pool, new, bid)
                    self.block_pool.decref(bid)
                    self.block_tables[i, blk] = new
                    self.slot_blocks[i][blk] = new
                    self.cow_count += 1
                    bid = new
                dst.append(bid * bs + p % bs)
                src_b.append(i)
                src_s.append(p - L)
        with self._phase("inputs"):
            idx = self._scatter_idx(dst, src_b, src_s)
        with self._phase("launch"):
            self.pool = self._scatter_kv_fn(self.pool, k_win, v_win, *idx)
        with self._phase("blocks"):
            for i, _ in live:
                self._spec_release_leases(i)

        emitted_total = 0
        rids: List[int] = []
        emitted: Dict[int, int] = {}
        with self._phase("emit"):
            for (i, r), toks_out in zip(live, outs):
                self.lengths[i] = min(int(self.lengths[i]) + len(toks_out),
                                      self.max_seq)
                for t in toks_out:
                    self._emit(r, i, t)
                emitted_total += len(toks_out)
                rids.append(r.rid)
                emitted[r.rid] = len(toks_out)
                if (toks_out[-1] == r.eos_id
                        or len(r.output) >= r.max_new_tokens):
                    completed.append(r)  # done_time stamped at end of step
                    r.status = DONE
                    self._free_slot(i)
        self.draft_tokens += drafted
        self.accepted_tokens += accepted
        self.scheduler.note_spec_step()
        return {"tokens": emitted_total, "rids": rids, "drafted": drafted,
                "verified": W * len(live), "accepted": accepted,
                "emitted": emitted}

    def _free_slot(self, i: int):
        self.slots[i] = None
        self._slot_row[i] = None
        self._slot_emit0[i] = 0
        if self.kv_layout == "paged":
            self._spec_release_leases(i)
            for bid in self.slot_blocks[i]:
                self.block_pool.decref(bid)
            self.slot_blocks[i] = []
            self.block_tables[i] = 0
            self.lengths[i] = 0
        else:
            self.lengths = self.lengths.at[i].set(0)

    def _sample(self, logits, req: Request) -> np.ndarray:
        """Sample one token per row of `logits` on the device and fetch
        them to the host."""
        with self._phase("sample"):
            self.key, sub = jax.random.split(self.key)
            toks = sample_tokens(jnp.asarray(logits), sub,
                                 temperature=req.temperature)
        with self._phase("fetch"):
            return np.asarray(toks)

    def _emit(self, req: Request, slot: int, tok: int):
        if req.first_token_time is None:
            req.first_token_time = self.clock()
        req.output.append(tok)

    # -- telemetry ----------------------------------------------------------

    def recent_tps(self, window: int = 50) -> float:
        log = [s for s in self.step_log[-window:]
               if s["kind"] in ("decode", "spec_verify")]
        if not log:
            return 0.0
        return sum(s["tokens"] for s in log) / max(sum(s["dt"] for s in log), 1e-9)


class EngineClient:
    """Submission facade over a shared `ServingEngine`.

    Several producers (a pod's routed queries, an executor's overlapping
    query sessions) hold clients onto ONE engine, so their requests occupy
    decode slots together — the cross-user batching a per-query
    `run_until_drained` loop never achieves. `submit` returns immediately
    with a `RequestHandle`; `settle` steps the shared engine until a set of
    handles is terminal (other users' requests make progress on the same
    steps)."""

    def __init__(self, engine: ServingEngine):
        self.engine = engine

    def submit(self, sreq: SessionRequest) -> RequestHandle:
        deadline = (None if sreq.deadline_s is None
                    else self.engine.clock() + sreq.deadline_s)
        req = Request(rid=self.engine.next_rid(), prompt=list(sreq.prompt),
                      max_new_tokens=sreq.max_new_tokens, eos_id=sreq.eos_id,
                      temperature=sreq.temperature, priority=sreq.priority,
                      deadline=deadline, tier=sreq.tier)
        return self.engine.submit(req)

    def step(self) -> List[Request]:
        return self.engine.step()

    def settle(self, handles: List[RequestHandle], *,
               max_steps: int = 100000) -> List[RequestHandle]:
        """Run the shared engine until every handle is terminal (done,
        cancelled or deadline-expired)."""
        for _ in range(max_steps):
            if all(h.done() for h in handles):
                return handles
            if not self.engine.has_work():
                break
            self.engine.step()
        if not all(h.done() for h in handles):
            raise EngineStallError(
                f"{sum(not h.done() for h in handles)} session(s) not "
                f"terminal after {max_steps} steps "
                f"(active={self.engine.active}, "
                f"waiting={len(self.engine.pending)})")
        return handles
