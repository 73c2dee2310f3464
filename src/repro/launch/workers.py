"""Multi-process fleet workers behind the frozen engine control protocol.

One worker process per pod/region, each owning a full `ServingEngine` (or an
`EngineExecutor` around one) and speaking the small serializable control
protocol from `serving/protocol.py` over a multiprocessing pipe:

    parent                          worker process
    ------                          --------------
    WorkerSpec.to_wire()  ───────▶  _worker_main: build engine, handshake
    {"op": "submit", request: …} ▶  EngineActor.handle("submit") → {"rid": …}
    {"op": "settle", rids: […]}  ▶  …run engine… → RequestResult wires
    {"op": "stats"}              ▶  EngineStats.to_wire()
    {"op": "shutdown"}           ▶  reply + exit

Every request crosses the boundary as a plain dict of primitives
(`session_request_to_wire`, `QuerySpec`, `RequestResult`, `EngineStats`) —
no jax arrays, no callables, no live engine references. Workers are spawned
with the **spawn** start method: fork is unsafe once jax has initialized its
backend in the parent, and a fresh interpreter lets each worker set
``XLA_FLAGS`` (forced host device count for `data_shards > 1`) *before* jax
spins up.

The virtual clock stays PER-WORKER — each engine runs its own timeline, and
the fleet aggregates wall-aligned snapshots: `rebase` pins a worker's clock
to the fleet schedule before a settle round (`clock.t = max(clock.t, t)`,
exactly what `run_fleet` does in-process), and `stats` ships the timeline
position back alongside the `EngineStats` payload.

This module's import footprint is deliberately tiny (stdlib +
`serving.protocol`): the spawn child imports it to locate `_worker_main`,
and nothing jax-flavoured may load before the environment is staged.
"""
from __future__ import annotations

import dataclasses
import multiprocessing as mp
import os
from typing import Any, Dict, List, Optional, Sequence

from repro.serving.protocol import (PROTOCOL_VERSION, EngineConfig,
                                    EngineStats, ProtocolError, QuerySpec,
                                    RequestResult, WorkerSpec,
                                    session_request_from_wire,
                                    session_request_to_wire)

# how long a parent waits for a worker's ready handshake by default: workers
# jit-compile their engine's bucketed kernels during construction, which on a
# cold CPU cache is minutes, not seconds
READY_TIMEOUT_S = 600.0
CALL_TIMEOUT_S = 600.0


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------


class EngineActor:
    """Op dispatcher around one engine — the worker-side half of the control
    protocol, also drivable in-process (the soak suite replays one event
    stream against a local engine and remote actors and diffs the results).

    Construction follows `WorkerSpec`: raw mode (`model_cfg` set) builds a
    bare `ServingEngine` from the serialized model config; executor mode
    builds an `EngineExecutor` so the full CarbonCall query surface (energy
    attribution, variant switching) is reachable over the wire.
    """

    def __init__(self, spec: WorkerSpec):
        self.spec = spec
        self.handles: Dict[int, Any] = {}      # rid -> RequestHandle
        self.queries: Dict[int, Any] = {}      # qid -> EngineSession
        self._next_qid = 0
        self.executor = None
        if spec.model_cfg is not None:
            self._build_raw(spec)
        else:
            self._build_executor(spec)

    # -- construction -------------------------------------------------------

    def _build_raw(self, spec: WorkerSpec):
        import jax

        from repro.config import (ModelConfig, MoEConfig, RuntimeConfig,
                                  SSMConfig)
        from repro.models import get_model
        from repro.quant import quantize_tree
        from repro.serving.engine import ServingEngine, VirtualClock
        from repro.sharding.param import init_params

        d = dict(spec.model_cfg)
        if isinstance(d.get("moe"), dict):
            d["moe"] = MoEConfig(**d["moe"])
        if isinstance(d.get("ssm"), dict):
            d["ssm"] = SSMConfig(**d["ssm"])
        if d.get("mrope_sections") is not None:
            d["mrope_sections"] = tuple(d["mrope_sections"])
        cfg = ModelConfig(**d)
        model = get_model(cfg)
        pspec = model.param_spec()
        params = init_params(pspec, jax.random.PRNGKey(spec.seed))
        self.variants = {v: quantize_tree(params, pspec, v)
                         for v in spec.config.variants}
        boot = spec.config.variants[0]
        self.engine = ServingEngine(cfg, self.variants[boot], RuntimeConfig(),
                                    config=spec.config,
                                    mesh=self._mesh(spec.config),
                                    clock=VirtualClock())
        self.engine.variant_name = boot
        self.client = self.engine.client()
        self.modes = None

    def _build_executor(self, spec: WorkerSpec):
        from repro.common.hardware import ORIN_AGX, TPU_V5E
        from repro.core.engine_executor import EngineExecutor
        from repro.core.executor import PAPER_MODELS
        from repro.core.power import modes_for

        hw_registry = {h.name: h for h in (ORIN_AGX, TPU_V5E)}
        if spec.hw not in hw_registry:
            raise ProtocolError(f"unknown hardware {spec.hw!r}; expected one "
                                f"of {sorted(hw_registry)}")
        hw = hw_registry[spec.hw]
        self.executor = EngineExecutor(
            PAPER_MODELS[spec.profile], hw, arch=spec.arch, seed=spec.seed,
            config=spec.config, tokens_per_call=spec.tokens_per_call,
            eval_tokens=spec.eval_tokens)
        self.engine = self.executor.engine
        self.client = self.executor.client
        self.variants = self.executor.variants
        self.modes = modes_for(hw)

    @staticmethod
    def _mesh(config: EngineConfig):
        if config.data_shards <= 1:
            return None
        from repro.launch.mesh import make_data_mesh
        return make_data_mesh(config.data_shards)

    # -- op dispatch ---------------------------------------------------------

    def handle(self, op: str, msg: Dict[str, Any]) -> Dict[str, Any]:
        fn = getattr(self, f"op_{op}", None)
        if fn is None:
            raise ProtocolError(f"unknown op {op!r}")
        return fn(msg)

    def _result_wire(self, rid: int) -> Dict[str, Any]:
        return RequestResult.from_request(
            self.handles[rid].request).to_wire()

    # engine-level ops (both modes)

    def op_submit(self, msg):
        h = self.client.submit(session_request_from_wire(msg["request"]))
        self.handles[h.rid] = h
        return {"rid": h.rid}

    def op_step(self, msg):
        done: List[int] = []
        for _ in range(int(msg.get("n", 1))):
            done.extend(r.rid for r in self.engine.step())
        return {"completed": done}

    def op_poll(self, msg):
        return {"status": self.handles[int(msg["rid"])].poll()}

    def op_cancel(self, msg):
        return {"cancelled": self.handles[int(msg["rid"])].cancel()}

    def op_swap(self, msg):
        name = msg["variant"]
        if name not in self.variants:
            raise ProtocolError(f"unknown variant {name!r}; worker holds "
                                f"{sorted(self.variants)}")
        self.engine.swap_params(self.variants[name], name)
        return {"variant": name, "swap_count": self.engine.swap_count}

    def op_advance(self, msg):
        self.engine.clock.advance(float(msg["dt"]))
        return {"t": self.engine.clock()}

    def op_rebase(self, msg):
        # fleet schedule anchor: never rewind a worker's own timeline
        self.engine.clock.t = max(self.engine.clock.t, float(msg["t"]))
        return {"t": self.engine.clock()}

    def op_clock(self, msg):
        return {"t": self.engine.clock()}

    def op_settle(self, msg):
        rids = [int(r) for r in msg["rids"]]
        self.client.settle([self.handles[r] for r in rids])
        return {"results": [self._result_wire(r) for r in rids],
                "t": self.engine.clock()}

    def op_results(self, msg):
        rids = msg.get("rids")
        if rids is None:
            rids = sorted(self.handles)
        return {"results": [self._result_wire(int(r)) for r in rids]}

    def op_drain(self, msg):
        n = 0
        for _ in range(int(msg.get("max_steps", 100_000))):
            if not self.engine.has_work():
                break
            n += len(self.engine.step())
        if self.engine.has_work():
            raise ProtocolError("engine failed to drain within step budget")
        return {"completed": n, "t": self.engine.clock()}

    def op_stats(self, msg):
        return {"stats": self.engine.stats().to_wire(),
                "t": self.engine.clock()}

    def op_check(self, msg):
        from repro.serving.invariants import check_invariants
        reqs = [h.request for _, h in sorted(self.handles.items())]
        return {"violations": check_invariants(
            self.engine, reqs, flush=bool(msg.get("flush", True)))}

    # executor-level ops (the CarbonCall query surface)

    def op_query(self, msg):
        if self.executor is None:
            raise ProtocolError("query ops need an executor-mode worker "
                                "(WorkerSpec without model_cfg)")
        q = QuerySpec.from_wire(msg["query"])
        mode = self.modes[q.mode_index % len(self.modes)]
        s = self.executor.begin_query(
            n_tools_in_prompt=q.n_tools, n_calls=q.n_calls,
            selection_correct=q.selection_correct, variant=q.variant,
            mode=mode, priority=q.priority, deadline_s=q.deadline_s,
            tier=q.tier)
        qid = self._next_qid
        self._next_qid += 1
        self.queries[qid] = s
        return {"qid": qid}

    def op_settle_queries(self, msg):
        if self.executor is None:
            raise ProtocolError("query ops need an executor-mode worker")
        qids = [int(q) for q in msg["qids"]]
        sessions = [self.queries[q] for q in qids]
        self.executor.settle(sessions)
        out = [dataclasses.asdict(self.queries.pop(q).execution)
               for q in qids]
        return {"executions": out,
                "stats": self.engine.stats().to_wire(),
                "t": self.engine.clock()}


def _worker_main(conn, spec_wire: Dict[str, Any]) -> None:
    """Worker process entry: stage the environment, build the actor, then
    serve the request/reply loop until shutdown or EOF. Runs in a SPAWNED
    interpreter — jax has not loaded yet, so the forced host device count
    for sharded configs can still take effect. The platform is the one the
    parent's environment names (JAX_PLATFORMS is inherited, not forced): a
    worker that cannot open its backend — e.g. a chip another process
    holds — fails its build, and the handshake carries the error back."""
    shards = int(dict(spec_wire.get("config") or {}).get("data_shards", 1))
    if shards > 1:
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={shards}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    try:
        spec = WorkerSpec.from_wire(spec_wire)
        actor = EngineActor(spec)
    except BaseException as e:           # ship build failures, don't hang
        try:
            conn.send({"ok": False, "ready": True,
                       "error": f"{type(e).__name__}: {e}"})
        finally:
            conn.close()
        return
    conn.send({"ok": True, "ready": True, "protocol": PROTOCOL_VERSION,
               "label": spec.label})
    while True:
        try:
            msg = conn.recv()
        except (EOFError, KeyboardInterrupt):
            break                        # parent went away: exit quietly
        op = msg.get("op", "")
        if op == "shutdown":
            conn.send({"ok": True})
            break
        try:
            conn.send({"ok": True, **actor.handle(op, msg)})
        except BaseException as e:       # errors are replies, not crashes
            conn.send({"ok": False, "error": f"{type(e).__name__}: {e}"})
    conn.close()


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------


class WorkerHandle:
    """Parent-side endpoint of one worker process.

    `call(op, **payload)` is the synchronous request/reply path; the
    `send`/`recv` halves are exposed separately so a fleet can dispatch one
    op to EVERY worker and then collect the replies — the workers run their
    settle rounds concurrently, which is the whole point of the exercise.
    """

    def __init__(self, spec: WorkerSpec, *, ctx=None):
        self.spec = spec
        self.label = spec.label or f"worker-{spec.seed}"
        ctx = ctx if ctx is not None else mp.get_context("spawn")
        self.conn, child = ctx.Pipe()
        self.proc = ctx.Process(target=_worker_main,
                                args=(child, spec.to_wire()), daemon=True)
        self.proc.start()
        child.close()                    # child's end lives in the child
        self._ready = False

    def wait_ready(self, timeout: float = READY_TIMEOUT_S) -> "WorkerHandle":
        """Block until the worker's handshake arrives (engine built)."""
        if self._ready:
            return self
        if not self.conn.poll(timeout):
            self.close()
            raise ProtocolError(
                f"worker {self.label!r}: no ready handshake in {timeout}s")
        try:
            msg = self.conn.recv()
        except EOFError:
            self.close()
            raise ProtocolError(
                f"worker {self.label!r} died before its handshake")
        if not msg.get("ok"):
            err = msg.get("error", "unknown failure")
            self.close()
            raise ProtocolError(f"worker {self.label!r} failed to build: "
                                f"{err}")
        if int(msg.get("protocol", -1)) != PROTOCOL_VERSION:
            self.close()
            raise ProtocolError(
                f"worker {self.label!r} speaks protocol "
                f"{msg.get('protocol')}, parent speaks {PROTOCOL_VERSION}")
        self._ready = True
        return self

    # -- async halves (fan-out) ---------------------------------------------

    def send(self, op: str, **payload) -> None:
        self.wait_ready()
        self.conn.send({"op": op, "v": PROTOCOL_VERSION, **payload})

    def recv(self, timeout: float = CALL_TIMEOUT_S) -> Dict[str, Any]:
        if not self.conn.poll(timeout):
            raise ProtocolError(f"worker {self.label!r}: no reply in "
                                f"{timeout}s")
        try:
            msg = self.conn.recv()
        except EOFError:
            raise ProtocolError(f"worker {self.label!r} died mid-call")
        if not msg.get("ok"):
            raise ProtocolError(f"worker {self.label!r}: "
                                f"{msg.get('error', 'unknown error')}")
        return msg

    # -- sync conveniences ---------------------------------------------------

    def call(self, op: str, **payload) -> Dict[str, Any]:
        self.send(op, **payload)
        return self.recv()

    def submit(self, sreq) -> int:
        return self.call("submit",
                         request=session_request_to_wire(sreq))["rid"]

    def query(self, qspec: QuerySpec) -> int:
        return self.call("query", query=qspec.to_wire())["qid"]

    def settle(self, rids: Sequence[int]) -> List[RequestResult]:
        return [RequestResult.from_wire(w)
                for w in self.call("settle", rids=list(rids))["results"]]

    def stats(self) -> EngineStats:
        return EngineStats.from_wire(self.call("stats")["stats"])

    def close(self, timeout: float = 10.0) -> None:
        """Shut the worker down; escalates to terminate if it won't die."""
        try:
            if self.proc.is_alive():
                self.conn.send({"op": "shutdown", "v": PROTOCOL_VERSION})
                self.proc.join(timeout)
        except (BrokenPipeError, OSError):
            pass
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(5.0)
        self.conn.close()


def launch_workers(specs: Sequence[WorkerSpec], *,
                   timeout: float = READY_TIMEOUT_S) -> List[WorkerHandle]:
    """Spawn one worker per spec and wait for every handshake. All workers
    build their engines CONCURRENTLY (each jit-warms its own kernels in its
    own process); any build failure tears the whole set down."""
    handles = [WorkerHandle(s) for s in specs]
    try:
        for h in handles:
            h.wait_ready(timeout)
    except BaseException:
        for h in handles:
            h.close()
        raise
    return handles


def launch_worker_fleet(fleet, *, seed: int = 0,
                        timeout: float = READY_TIMEOUT_S
                        ) -> List[WorkerHandle]:
    """Back every pod of a built `Fleet` (or a `FleetSpec`) with its own
    worker process: each worker receives the pod's serializable
    `EngineConfig` — the same payload `ensure_client` would size an
    in-process engine from — and is attached as `pod.worker`, which flips
    the router's predicted-wait logic onto protocol-shipped `EngineStats`.
    Returns the handles in `fleet.pods` order; callers own shutdown."""
    from repro.core.fleet import Fleet, FleetSpec, build_fleet

    if isinstance(fleet, FleetSpec):
        fleet = build_fleet(fleet, seed=seed)
    assert isinstance(fleet, Fleet)
    specs = [WorkerSpec(config=(p.engine_cfg if p.engine_cfg is not None
                                else EngineConfig()),
                        seed=seed + p.pod_id,
                        label=f"{p.region}/pod{p.pod_id}")
             for p in fleet.pods]
    workers = launch_workers(specs, timeout=timeout)
    for pod, w in zip(fleet.pods, workers):
        pod.worker = w
    return workers


def shutdown_workers(workers: Sequence[Optional[WorkerHandle]]) -> None:
    for w in workers:
        if w is not None:
            w.close()
