"""What every benchmark entry point shares: finding a cell's files and its
architecture's plug-in, the compile cache, building the served model from the
seed, warming it up, the open-loop window, and the sample the correctness
check reads.

The system under test is `repro.serving.ServingEngine`, driven through
`EngineClient.submit` and `ServingEngine.step()` in this process. Nothing
here changes the program; it only builds its inputs and reads its outputs
and counters.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
ARCHS = BENCH / "archs"
# a fixed path inside the checkout: the path is part of the cache's key
CACHE_DIR = BENCH / ".jax_cache"
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import traffic as traffic_mod  # noqa: E402
from bench import weights as weights_mod  # noqa: E402

# rehearsal on a CPU: every length cut by this, every width to the
# architecture's REHEARSE_DIMS, kernels in interpret mode
REHEARSE_SCALE = 1 / 8


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache():
    """JAX's persistent cache at the checkout's fixed directory, overriding
    any directory the environment names, for every program however small
    or quick to compile. Call before the first compile."""
    import jax
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict               # bench/configs/<config>.json
    mix: dict               # bench/traffic/<traffic>.json
    spec: dict              # the whole BENCHMARK.json

    @property
    def variant(self) -> str:
        return self.mix["variant"]

    @property
    def arch(self):
        """The configuration's architecture plug-in (`load_arch`)."""
        return load_arch(self.cfg)

    def limits(self) -> dict:
        """bench/limits/<cell>.json: each compared number's limit."""
        return json.loads((BENCH / "limits" / f"{self.name}.json").read_text())


def load_arch(cfg: dict):
    """`bench/archs/<name>.py` for the configuration's `"bench_arch": <name>`,
    loaded by its path once a process, so that its jitted programs are
    shared by every caller. It holds all the benchmark knows of the
    architecture; `bench/archs/qwen2.py` lists what it provides."""
    name = cfg.get("bench_arch")
    if not name:
        raise KeyError(
            f"configuration {cfg.get('arch', '?')!r} names no architecture "
            "plug-in: give its file \"bench_arch\": \"<name>\" for "
            f"{ARCHS.relative_to(ROOT)}/<name>.py")
    key = f"bench_arch_{name}"
    if key not in sys.modules:
        path = ARCHS / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no architecture plug-in {path} for "
                                    f"\"bench_arch\": {name!r}")
        spec = importlib.util.spec_from_file_location(key, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        sys.modules[key] = module
    return sys.modules[key]


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str) -> Cell:
    """A workload named in BENCHMARK.json, or `<config>.<traffic>` for a
    pairing no cell declares (the knee sweep and the fault witness)."""
    spec = load_spec()
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        config, _, traffic = name.partition(".")
        entry = {"name": name, "config": config, "traffic": traffic, "chips": 1}
    conf = next((c for c in spec["configs"] if c["name"] == entry["config"]),
                None)
    cfg_path = ROOT / conf["file"] if conf else \
        BENCH / "configs" / f"{entry['config']}.json"
    mix = traffic_mod.load(BENCH / "traffic" / f"{entry['traffic']}.json")
    return Cell(entry["name"], int(entry["chips"]),
                json.loads(cfg_path.read_text()), mix, spec)


class Served:
    """The served deployment of one cell at one seed: every variant the
    configuration keeps resident, drawn on the device from the seed, and a
    factory for engines over the cell's variant."""

    def __init__(self, cell: Cell, seed: int, *, rehearse: bool = False):
        import jax
        from repro.config import RuntimeConfig
        from repro.models import get_model
        from repro.serving import EngineConfig

        self.cell = cell
        self.rehearse = rehearse
        arch = cell.arch
        cfg = cell.cfg
        dims = arch.dims_of(cfg)
        if rehearse:
            dims.update(arch.REHEARSE_DIMS)
        self.dims = dims
        self.model_cfg = arch.model_config(cfg, dims)
        self.rcfg = (RuntimeConfig(use_pallas=True, interpret=True)
                     if rehearse else RuntimeConfig())
        e = dict(cfg["engine"])
        scale = REHEARSE_SCALE if rehearse else 1.0
        self.econfig = EngineConfig(
            max_batch=e["max_batch"], max_seq=int(e["max_seq"] * scale),
            prompt_buckets=tuple(int(b * scale) for b in e["prompt_buckets"]),
            kv_layout=e["kv_layout"], kv_cache_dtype=e["kv_cache_dtype"],
            block_size=e["block_size"], variants=tuple(cfg["variants"]))
        self.scale = scale
        self.w = arch.draw(dims, tuple(cfg["variants"]),
                           weights_mod.seed_key(seed))
        jax.block_until_ready(self.w)
        spec = get_model(self.model_cfg).param_spec()
        self.params = {f: arch.program_params(self.w, f, spec)
                       for f in cfg["variants"]}

    def engine(self):
        from repro.serving import ServingEngine
        eng = ServingEngine(self.model_cfg, self.params[self.cell.variant],
                            self.rcfg, config=self.econfig)
        eng.variant_name = self.cell.variant
        return eng

    def arrivals(self, seconds: float, seed: int):
        return traffic_mod.generate(self.cell.mix, seconds, seed,
                                    self.dims["V"], scale=self.scale)

    def check_shape(self, sample_size: int = 8) -> dict:
        """The longest sequence and the most served positions the traffic
        can give the reference, so that every run of the cell compiles it
        once."""
        mix = self.cell.mix
        pre = mix["prefixes"]["length"] if mix["prefixes"]["count"] else 0
        longest = pre + max(mix["prompt_len"].get("max", 0),
                            mix["prompt_len"].get("value", 0))
        out = max(2, round(mix["output_len"]["max"] * self.scale))
        return {"seq_len": round(longest * self.scale) + out,
                "positions": sample_size * out}

    def free_program(self):
        """Drop every program object and every variant but the served one,
        so that the reference has the device to itself."""
        self.params = None
        for fmt in list(self.cell.cfg["variants"]):
            if fmt != self.cell.variant:
                self.w = weights_mod.drop_format(self.w, fmt)
        gc.collect()


def _pow2(n: int, cap: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return min(p, cap)


def admission_classes(b: int, hit: int, bs: int, B: int,
                      max_seq: int) -> List[list]:
    """Per-row cached prefix lengths of admissions that, between them, run
    every program an admission of the traffic can run.

    The engine pads an admission to B rows of the bucket `b`. With no row
    hitting the prefix cache it runs the cold prefill and scatters k * b
    positions into the pool. Otherwise it runs the suffix prefill over
    s = pow2(b - least cached length) positions (at most b) against
    p = pow2(most cached blocks) gathered blocks, and scatters the rows'
    uncached positions, n = pow2(their count). The suffix prefill's program
    is fixed by (s, p), the scatter's by (s, n). Cached lengths are block
    multiples up to `hit`, as LRU eviction can cut a cached chain at any
    block. Admissions are picked greedily, each covering as many programs
    not yet run as it can."""
    big = 1 << 62
    cover: List[list] = [[0] * k for k in range(1, B + 1)
                         if _pow2(k * b, big) != _pow2((k - 1) * b, big)]
    grid = list(range(bs, hit + 1, bs))
    blocks_per_slot = -(-max_seq // bs)
    cands: Dict[tuple, list] = {}
    for k in range(1, B + 1):
        for lo in ([0] + grid if k > 1 else grid):
            for hi in (g for g in grid if g >= lo):
                if k == 1 and hi != lo:
                    continue
                for mid in ([lo] if k <= 2 else [g for g in [0] + grid
                                                 if lo <= g <= hi]):
                    rows = ([lo] if k == 1 else [lo, hi] + [mid] * (k - 2))
                    s_suf = _pow2(b - lo, b)
                    p = _pow2(-(-hi // bs), blocks_per_slot)
                    n = _pow2(sum(b - c for c in rows), big)
                    cands.setdefault((("prefix", s_suf, p),
                                      ("scatter", s_suf, n)), rows)
    todo = {key for pair in cands for key in pair}
    while todo:
        pair = max(cands, key=lambda pr: sum(x in todo for x in pr))
        todo -= set(pair)
        cover.append(cands.pop(pair))
    return cover


def warm_up(served: Served, seed: int) -> int:
    """Run, on a throw-away engine, every admission shape the cell's traffic
    can produce and the decode step, so that the window compiles nothing.

    An admission is padded to the bucket `b` of its longest prompt, so each
    bucket that a prompt of the mix (prefix and turn, least to most) falls
    in is warmed, with the admission classes (`admission_classes`) of the
    most a row padded to `b` can find cached: the zeros of its left padding
    (up to `b` less the mix's shortest prompt) and then the shared prefix.
    The warm-up's prompts are `b` tokens long, so that each row hits just
    the cached length its class gives it; the programs depend on `b` and
    the cached lengths alone. Each class starts from one cached row that its
    hitting rows share exactly as many tokens with as they should hit.
    Returns the number of warm-up requests."""
    from repro.serving import SessionRequest
    mix, ec = served.cell.mix, served.econfig
    bs, B = ec.block_size, ec.max_batch
    pre = mix["prefixes"]
    pre_len = max(1, round(pre["length"] * served.scale)) if pre["count"] else 0
    turn_lo, turn_hi = traffic_mod.length_range(mix["prompt_len"],
                                                served.scale)
    buckets = sorted(set(ec.prompt_buckets) | {ec.max_seq})

    def bucket(n):
        return next((x for x in buckets if x >= n), buckets[-1])

    shortest = pre_len + turn_lo
    reached = sorted({bucket(pre_len + t)
                      for t in range(turn_lo, turn_hi + 1)})
    rng = np.random.default_rng(seed ^ 0x5EED)
    V = served.dims["V"]

    def fresh(n):
        return rng.integers(2, V, n).tolist()

    eng = served.engine()
    client = eng.client()
    n = 0
    # a preempted request re-prefills its padded row and the tokens it had,
    # right-padded to a power of two: the cold prefill at that width, which
    # a cold admission of a prompt in that bucket also runs
    out_max = max(2, round(mix["output_len"].get("max", 0) * served.scale))
    widths = {_pow2(L, ec.max_seq) for b in reached
              for L in range(b + 1, min(b + out_max, ec.max_seq) + 1)}
    for width in sorted(widths - set(reached)):
        if width not in buckets:
            log(f"warm-up: a resumed row of width {width} has no bucket of "
                "its own; the window may compile")
            continue
        client.settle([client.submit(SessionRequest(
            prompt=fresh(width), max_new_tokens=2, eos_id=-1))])
        n += 1
    for b in reached:
        hit = max(0, min((b - shortest + pre_len) // bs * bs, b - bs))
        base = None
        for rows in admission_classes(b, hit, bs, B, ec.max_seq):
            if any(rows) and base is None:
                base = fresh(b)
                client.settle([client.submit(SessionRequest(
                    prompt=base, max_new_tokens=2, eos_id=-1))])
                n += 1
            prompts = []
            for c in rows:
                tail = fresh(b - c)
                if c and tail[0] == base[c]:
                    tail[0] = 2 + (tail[0] - 1) % (V - 2)
                prompts.append((base[:c] if c else []) + tail)
            client.settle([client.submit(SessionRequest(
                prompt=p, max_new_tokens=2, eos_id=-1)) for p in prompts])
            admitted = [r for r in eng.step_log if r["kind"] == "prefill"][-1]
            if admitted["cached_tokens"] != sum(rows):
                log(f"warm-up: an admission meant to hit {sum(rows)} cached "
                    f"tokens hit {admitted['cached_tokens']}; the window "
                    "may compile")
            n += len(prompts)
            # the newest rows stay cached longest: the next class hits them
            base = prompts[-1]
    del client, eng
    gc.collect()
    return n


@dataclasses.dataclass
class Record:
    """One request of the window, timed by the benchmark's clock from the
    moment it was due."""
    due: float
    prompt_len: int
    asked: int
    handle: object = None
    submitted: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    # what the engine's request held when the window was detached
    done: bool = False
    prompt: Optional[List[int]] = None
    output: Optional[List[int]] = None
    queue_wait_s: float = 0.0

    @property
    def request(self):
        return self.handle.request


@dataclasses.dataclass
class Step:
    """One engine step, in window seconds, with the engine's own record of
    it (`ServingEngine.step_log`)."""
    start: float
    end: float
    kind: str
    active: int
    rows: int               # requests the step admitted or decoded
    computed: int           # prompt tokens it ran the model over
    cached: int             # prompt tokens it took from the prefix cache


@dataclasses.dataclass
class Window:
    seconds: float
    records: List[Record]
    steps: List[Step]
    drained_at: float
    kernel_fallbacks: int       # paged decode steps off the Pallas kernel
    compiles: int

    def detach(self):
        """Copy out what the records need and drop every handle, so that
        the engine can be freed."""
        for r in self.records:
            if r.handle is not None:
                req = r.handle.request
                r.done = r.handle.done()
                r.prompt, r.output = list(req.prompt), list(req.output)
                r.queue_wait_s = float(req.queue_wait_s)
                r.handle = None


class CompileCounter:
    """Backend compiles, from JAX's monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.event:
            self.count += 1


def run_window(engine, arrivals, seconds: float, *, drain_s: float = 60.0,
               compiles: Optional[CompileCounter] = None,
               on_open=None, on_close=None) -> Window:
    """Submit each arrival when it is due, step the engine whenever it has
    work, and sleep until the next arrival when it has none. Every token is
    stamped when the step that made it returns. After `seconds` nothing new
    arrives; the engine is stepped until every request has finished, for at
    most `drain_s` more after `on_close` returns. `on_open`/`on_close` run
    at the window's edges (the profiler's start and stop)."""
    import jax
    from repro.serving import SessionRequest
    Ann = jax.profiler.TraceAnnotation
    recs = [Record(a.due_s, len(a.prompt), a.max_new_tokens)
            for a in arrivals]
    prompts = [a.prompt for a in arrivals]
    client = engine.client()
    steps: List[Step] = []
    live: List[Record] = []
    c0 = compiles.count if compiles else 0
    if on_open:
        on_open()
    span = Ann("bench.window")
    span.__enter__()
    # name whatever compiles in the window (there should be nothing)
    jax.config.update("jax_log_compiles", True)
    t0 = time.perf_counter()
    nxt = 0
    closed = False
    deadline = 0.0
    while True:
        now = time.perf_counter() - t0
        if not closed and now >= seconds:
            closed = True
            span.__exit__(None, None, None)
            jax.config.update("jax_log_compiles", False)
            c_window = (compiles.count if compiles else 0) - c0
            if on_close:
                on_close()
            now = time.perf_counter() - t0
            deadline = now + drain_s
        if closed and (not live or now >= deadline):
            break
        while nxt < len(recs) and recs[nxt].due <= now:
            r = recs[nxt]
            with Ann("bench.submit"):
                r.handle = client.submit(SessionRequest(
                    prompt=prompts[nxt], max_new_tokens=r.asked, eos_id=-1,
                    temperature=0.0))
            r.submitted = now
            live.append(r)
            nxt += 1
        if engine.has_work():
            with Ann("bench.step"):
                engine.step()
            end = time.perf_counter() - t0
            rec = engine.step_log[-1]
            steps.append(Step(now, end, rec["kind"], rec["active"],
                              len(rec["rids"]), rec["prompt_tokens"],
                              rec["cached_tokens"]))
            still = []
            for r in live:
                for _ in range(len(r.request.output) - len(r.times)):
                    r.times.append(end)
                if not r.handle.done():
                    still.append(r)
            live = still
        else:
            wait = (recs[nxt].due if nxt < len(recs) else seconds) - now
            if not closed and wait > 0:
                with Ann("bench.wait"):
                    time.sleep(min(wait, max(seconds - now, 0.0)))
    win = Window(seconds, recs, steps, time.perf_counter() - t0,
                 engine.kernel_fallbacks, c_window)
    win.detach()
    return win


def sample_for_check(win: Window, seed: int, *, want: int = 8) -> List[Record]:
    """The finished requests the reference reads: the one with the most
    served tokens, the first one served (admitted cold), and others drawn
    from the seed, up to `want`."""
    done = [r for r in win.records if r.done and r.times]
    if not done:
        return []
    picked = [max(done, key=lambda r: (len(r.times), -r.due)),
              min(done, key=lambda r: r.times[0])]
    rest = [r for r in done if all(r is not p for p in picked)]
    rng = np.random.default_rng(seed ^ 0xC0FFEE)
    for i in rng.permutation(len(rest))[:max(0, want - len(picked))]:
        picked.append(rest[int(i)])
    out = []
    for r in picked:
        if all(r is not o for o in out):
            out.append(r)
    return out
