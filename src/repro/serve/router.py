"""Multi-replica front-end: prefix-affinity routing over engine subprocesses.

The :class:`Router` owns the GLOBAL :class:`RequestQueue` and spreads
sessions across N :class:`~repro.serve.step.UnifiedServeEngine` replicas,
each a subprocess worker (``repro.serve.replica``) speaking the
length-prefixed frame protocol.  One ``step()``:

    dispatch   pop queued requests, score replicas, admit over the pipe
    compute    broadcast ``step`` to every busy replica, THEN collect —
               the replicas run their waves concurrently, so aggregate
               tok/s scales with the replica count (benchmarks gate this)
    collect    fold finished requests (tokens + latency bookkeeping) back
               into router-global results

Routing policies (``route=``):

    prefix        score replicas by EXPECTED resident-prefix-hit tokens —
                  the prompt's block-aligned chain hashes (the exact
                  content hash ``block_pool.py`` registers blocks under)
                  walked against each replica's published-prefix set; a
                  cold prefix falls back to least-loaded
    rr            round-robin
    least-loaded  fewest outstanding prompt+decode tokens

plus a sticky session map layered on top: a multi-turn ``session=`` re-hits
the replica that already holds its KV, whatever the policy says.

A replica that answers ``{"full"}`` (admission cap) gets skipped for the
next-best candidate; if every replica is full the request is *bounced* —
:meth:`RequestQueue.bounce` re-queues it at the front with its ORIGINAL
``arrival_ns``, so TTFT keeps counting across the bounce.  A replica whose
pipe dies mid-protocol is declared dead: its published prefixes and sticky
sessions are dropped and its in-flight requests bounce to the survivors.

Disaggregation (``disaggregate=True``): the first ``num_prefill`` replicas
serve ONLY prompts (admitted with ``max_new_tokens=1`` so they retire at
prefill, publishing every full prompt block into their prefix cache), and
the rest only decode.  Finished KV blocks stream prefill -> decode as a
spill file in the quantized wire format (``replica.save_spill``); the
decode replica imports them under the same chain hashes, so its admission
of the full request prefix-hits the transferred blocks instead of
recomputing the prompt — and because the decode admission carries the
original ``arrival_ns``, its ``EV_REQ_TTFT_US`` measures TTFT end-to-end
ACROSS the handoff.  ``EV_KV_XFER_BYTES`` / ``EV_KV_XFER_US`` on the
router's stream record every transfer.

Tracing: the router is TASK 0 of a ``host_device`` process model spanning
``1 + N`` tasks; every routing decision is punctual ``EV_ROUTE_DECISION``
(value = chosen replica's task id) next to ``EV_ROUTE_PREFIX_HITS``.  At
:meth:`close` the workers flush per-task segment streams and the router
k-way merges them with its own records into ONE ``.prv`` — every replica
is a row group in the same Paraver timeline (docs/router.md).

One chip belongs to one process: on a TPU host each replica process is
pinned to its own chip (:func:`replica_envs`), more replicas than chips is
refused, and a router whose own process already holds the TPU refuses to
start children that would need it.
"""
from __future__ import annotations

import glob
import os
import pathlib
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from repro.core import events as ev
from repro.serve.block_pool import _block_hash
from repro.serve.queue import Request, RequestQueue, RequestState
from repro.serve.replica import read_frame, write_frame

ROUTE_MODES = ("prefix", "rr", "least-loaded")


class ReplicaDead(RuntimeError):
    """The worker's pipe closed mid-protocol (crash or kill)."""


class PrefixAffinity:
    """Expected-prefix-hit scorer over router-side published-prefix sets.

    Pure bookkeeping — no subprocesses — so the scoring policy is unit-
    testable on its own: :meth:`publish` records the chain hashes a
    replica's pool will register after serving a prompt, :meth:`score`
    walks a candidate prompt's chain against each set and returns the
    expected hit TOKENS (leading resident run x block_size, the same
    longest-prefix-run rule ``BlockPool.resolve_hits`` applies)."""

    def __init__(self, block_size: int):
        self.block_size = int(block_size)
        self.resident: dict[int, set[int]] = {}

    def chain(self, prompt) -> list[int]:
        """Block-aligned chain hashes — identical to
        ``BlockPool.hash_chain`` so router-side expectations and worker-
        side registrations agree on content identity."""
        bs = self.block_size
        out, parent = [], 0
        for j in range(len(prompt) // bs):
            parent = _block_hash(parent, prompt[j * bs:(j + 1) * bs])
            out.append(parent)
        return out

    def add_replica(self, idx: int):
        self.resident.setdefault(idx, set())

    def drop_replica(self, idx: int):
        self.resident.pop(idx, None)

    def publish(self, idx: int, prompt):
        self.resident.setdefault(idx, set()).update(self.chain(prompt))

    def publish_hashes(self, idx: int, hashes):
        self.resident.setdefault(idx, set()).update(int(h) for h in hashes)

    def reset_hashes(self, idx: int, hashes):
        """Replace a replica's set with worker-reported truth (evictions
        make optimistic publishes go stale)."""
        self.resident[idx] = {int(h) for h in hashes}

    def score(self, prompt, candidates) -> dict[int, int]:
        chain = self.chain(prompt)
        out = {}
        for idx in candidates:
            res = self.resident.get(idx, ())
            hits = 0
            for h in chain:
                if h not in res:
                    break
                hits += 1
            out[idx] = hits * self.block_size
        return out


class ReplicaHandle:
    """One worker subprocess + its half of the frame protocol."""

    def __init__(self, idx: int, task_id: int, proc: subprocess.Popen,
                 role: str):
        self.idx = idx
        self.task_id = task_id
        self.proc = proc
        self.role = role  # "unified" | "prefill" | "decode"
        self.alive = True
        self.stats: dict = {}
        self.segments: list[str] = []

    def send(self, obj):
        if not self.alive:
            raise ReplicaDead(f"replica {self.idx} is dead")
        try:
            write_frame(self.proc.stdin, obj)
        except (BrokenPipeError, OSError) as e:
            raise ReplicaDead(f"replica {self.idx}: {e}") from e

    def recv(self) -> dict:
        if not self.alive:
            raise ReplicaDead(f"replica {self.idx} is dead")
        frame = read_frame(self.proc.stdout)
        if frame is None:
            raise ReplicaDead(f"replica {self.idx}: pipe EOF")
        return frame

    def call(self, obj) -> dict:
        self.send(obj)
        return self.recv()

    def kill(self):
        self.alive = False
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


# device files of attached TPU chips (v4 exposes accel nodes, v5e+ VFIO)
TPU_CHIP_GLOBS = ("/dev/accel[0-9]*", "/dev/vfio/[0-9]*")
TPU_PORT_BASE = 8476  # per-replica libtpu mesh-controller port


def local_tpu_chips() -> int:
    """TPU chips attached to this host, counted from their device files —
    never through JAX, whose backend init would take the chips."""
    return sum(len(glob.glob(pat)) for pat in TPU_CHIP_GLOBS)


def _parent_holds_tpu() -> bool:
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return (xla_bridge.backends_are_initialized()
            and "tpu" in xla_bridge.backends())


def replica_envs(num_replicas: int, env: dict,
                 chips: int | None = None) -> list[dict]:
    """One environment per replica process.

    Children pinned to the CPU (``JAX_PLATFORMS=cpu``), or on a host with no
    TPU, share ``env``.  Otherwise replica ``r`` sees chip ``r`` alone
    through libtpu's per-process chip-visibility settings.  More replicas
    than chips is an error, and so is a parent process that holds the TPU
    itself: either would leave two processes on one chip, which fail or
    hang.
    """
    platforms = env.get("JAX_PLATFORMS", "")
    chips = local_tpu_chips() if chips is None else chips
    if platforms == "cpu" or chips == 0:
        return [dict(env) for _ in range(num_replicas)]
    if num_replicas > chips:
        raise RuntimeError(
            f"{num_replicas} replicas need one TPU chip each, but this host "
            f"has {chips}; serve fewer replicas (or set JAX_PLATFORMS=cpu "
            f"in worker_env to run them on the CPU)")
    if _parent_holds_tpu():
        raise RuntimeError(
            "this process already holds the TPU, so its replica processes "
            "could not open their chips; start the Router before anything "
            "initializes JAX on the TPU")
    return [{**env, "TPU_VISIBLE_CHIPS": str(r),
             "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
             "TPU_PROCESS_BOUNDS": "1,1,1",
             "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{TPU_PORT_BASE + r}",
             "TPU_MESH_CONTROLLER_PORT": str(TPU_PORT_BASE + r)}
            for r in range(num_replicas)]


class Router:
    """Front-end router over N replica subprocesses (see module docstring).

    ``engine`` kwargs are forwarded to every worker's
    ``UnifiedServeEngine``; ``per_replica={r: {...}}`` overlays per-index
    engine kwargs (e.g. a spec lane on one replica — greedy output stays
    bit-identical, so heterogeneous fleets are legal).  Every replica
    builds identical params from ``PRNGKey(param_seed)`` over the same
    reduced config, which is what makes routed greedy output per-request
    bit-identical to a single local engine."""

    def __init__(self, arch: str = "granite-8b", *, num_replicas: int = 2,
                 route: str = "prefix", disaggregate: bool = False,
                 num_prefill: int = 1, reduced: dict | None = None,
                 cfg: dict | None = None, engine: dict | None = None,
                 per_replica: dict[int, dict] | None = None,
                 max_inflight: int | None = None, wire_dtype: str | None = None,
                 trace: bool = False, trace_dir=None,
                 app_name: str = "serve-router", worker_env: dict | None = None,
                 param_seed: int = 0, persist_sessions: bool = False):
        if route not in ROUTE_MODES:
            raise ValueError(f"route must be one of {ROUTE_MODES}, got {route!r}")
        if num_replicas < 1:
            raise ValueError("num_replicas must be >= 1")
        if disaggregate and num_replicas < 2:
            raise ValueError("--disaggregate needs >= 2 replicas "
                             "(>=1 prefill + >=1 decode)")
        self.route = route
        # sticky routing always follows the session key; with
        # ``persist_sessions`` the key is ALSO forwarded to the worker
        # engine, whose session pin keeps the conversation's KV blocks
        # resident across turns (engine.submit then requires each turn to
        # extend the stored context — opt-in, because sticky-only callers
        # reuse keys across unrelated prompts)
        self.persist_sessions = bool(persist_sessions)
        self.disaggregate = bool(disaggregate)
        self.num_prefill = int(num_prefill) if disaggregate else 0
        engine = dict(engine or {})
        self.block_size = int(engine.get("block_size", 16))
        kv_dtype = (cfg or {}).get("kv_dtype", "fp16")
        # lossless wire for an already-quantized pool (raw storage + scale
        # leaves pass through); int8 wire compresses an fp16 pool's handoff
        self.wire_dtype = wire_dtype or (kv_dtype if kv_dtype != "fp16"
                                         else "int8")

        self.queue = RequestQueue()
        self.affinity = PrefixAffinity(self.block_size)
        self.session_of: dict = {}  # session key -> replica idx (sticky)
        self._rr = 0
        self.results: dict[int, np.ndarray] = {}
        self.request_info: dict[int, dict] = {}  # grid -> worker-side latency
        self._session_key: dict[int, object] = {}  # grid -> session
        self.stats = {"route_decisions": 0, "bounces": 0, "deaths": 0,
                      "expected_hit_tokens": 0, "prefix_hit_tokens": 0,
                      "prompt_tokens": 0, "kv_xfer_bytes": 0,
                      "kv_xfer_us": 0, "kv_xfers": 0}

        self.t0_ns = time.perf_counter_ns()
        self.tracer = None
        self._own_trace_dir = False
        if trace_dir is None and (trace or disaggregate):
            trace_dir = tempfile.mkdtemp(prefix="serve-router-")
            self._own_trace_dir = True
        self.trace_dir = pathlib.Path(trace_dir) if trace_dir else None
        if trace:
            from repro.core.tracer import Tracer

            self.tracer = Tracer(app_name, mode="host_device")
            self.tracer.pm.bind_host(0, 1 + num_replicas)
            self.tracer.init(t0_ns=self.t0_ns)
            self._register_types(num_replicas, engine.get("num_slots", 4))

        src = str(pathlib.Path(__file__).resolve().parents[2])
        env = {**os.environ}
        env["PYTHONPATH"] = src + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        env.update(worker_env or {})
        envs = replica_envs(num_replicas, env)

        self.handles: list[ReplicaHandle] = []
        self.pending: list[dict[int, Request]] = []  # per replica: grid -> req
        self.load: list[int] = []  # outstanding prompt+decode tokens
        for r in range(num_replicas):
            role = ("prefill" if disaggregate and r < self.num_prefill
                    else "decode" if disaggregate else "unified")
            # -c (not -m): serve/__init__ imports repro.serve.replica, so
            # runpy would warn about re-executing an already-imported module
            cmd = [sys.executable, "-c",
                   "import sys; from repro.serve.replica import main; "
                   "sys.exit(main())",
                   "--task-id", str(1 + r),
                   "--num-tasks", str(1 + num_replicas),
                   "--t0-ns", str(self.t0_ns)]
            if trace:
                cmd += ["--trace-base", str(self.trace_dir / f"replica{r}")]
            proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                    stdout=subprocess.PIPE, env=envs[r])
            h = ReplicaHandle(r, 1 + r, proc, role)
            ekw = dict(engine)
            ekw.update((per_replica or {}).get(r, {}))
            h.send({"op": "init", "arch": arch, "reduced": reduced or {},
                    "cfg": cfg or {}, "engine": ekw,
                    "param_seed": param_seed, "max_inflight": max_inflight})
            self.handles.append(h)
            self.pending.append({})
            self.load.append(0)
            self.affinity.add_replica(r)
        for h in self.handles:  # workers build engines concurrently
            hello = h.recv()
            if "error" in hello:
                raise RuntimeError(
                    f"replica {h.idx} failed to start: {hello['error']}")
            h.num_blocks = int(hello["num_blocks"])
            h.max_inflight = int(hello["max_inflight"])

    # ------------------------------------------------------------------
    def _register_types(self, num_replicas: int, num_slots: int):
        tr = self.tracer
        tr.register(ev.EV_ROUTE_DECISION,
                    ev.ROUTER_EVENT_LABELS[ev.EV_ROUTE_DECISION],
                    {1 + r: f"replica {r}" for r in range(num_replicas)})
        # the merged .pcf comes from the ROUTER's tracer: register the
        # serve/kernel counter labels the replica engines will emit so
        # their merged streams decode by name in Paraver
        for code, label in ev.SERVE_CTR_LABELS.items():
            tr.register(code, label)
        for code, label in ev.KERNEL_EVENT_LABELS.items():
            tr.register(code, label)
        tr.register(ev.EV_REQ_ADMIT, "Serve request admitted (rid+1)")
        tr.register(ev.EV_REQ_RETIRE, "Serve request retired (rid+1)")
        tr.register(ev.EV_REQ_PREEMPT, "Serve request preempted (rid+1)")
        tr.register(ev.EV_FORK, "CoW fork: child stream minted (parent rid+1)")
        tr.register(ev.EV_EVICT, "KV block evicted (block id)")
        for s in range(num_slots):
            tr.register(ev.EV_SLOT_BASE + s,
                        f"Serve slot {s} occupant (rid+1)", {0: "empty"})

    def _emit(self, code: int, value: int):
        if self.tracer is not None:
            self.tracer.emit(code, value)

    # ------------------------------------------------------------------
    # intake
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, *, session=None,
               arrival_ns: int | None = None, n_samples: int = 1) -> Request:
        """``n_samples > 1`` fans out on the WORKER (CoW fork at prompt
        completion) — the router routes the whole fan as one unit, so all
        n streams share one replica's prompt blocks instead of prefilling
        the prompt n times across the fleet.  ``session=`` is both the
        sticky-routing key and the worker-side persistent-context id."""
        req = self.queue.submit(prompt, max_new_tokens,
                                arrival_ns=arrival_ns, n_samples=n_samples,
                                session=str(session) if session is not None
                                else None)
        if session is not None:
            self._session_key[req.rid] = session
        return req

    # ------------------------------------------------------------------
    # routing
    # ------------------------------------------------------------------
    def _alive(self, roles=("unified", "decode")) -> list[ReplicaHandle]:
        return [h for h in self.handles if h.alive and h.role in roles]

    def _candidates(self, req: Request) -> list[ReplicaHandle]:
        """Serving replicas ordered best-first for this request."""
        alive = self._alive()
        if not alive:
            raise RuntimeError("all serving replicas are dead")
        session = self._session_key.get(req.rid)
        if session is not None and session in self.session_of:
            sticky = self.session_of[session]
            alive.sort(key=lambda h: (h.idx != sticky, self.load[h.idx]))
            return alive
        if self.route == "rr":
            order = {h.idx: (h.idx - self._rr) % (max(x.idx for x in alive) + 1)
                     for h in alive}
            alive.sort(key=lambda h: order[h.idx])
            self._rr += 1
            return alive
        if self.route == "least-loaded":
            alive.sort(key=lambda h: self.load[h.idx])
            return alive
        # prefix: expected hit tokens desc, load asc; all-cold == least-loaded
        scores = self.affinity.score(req.prompt, [h.idx for h in alive])
        alive.sort(key=lambda h: (-scores[h.idx], self.load[h.idx]))
        return alive

    def _admit_on(self, h: ReplicaHandle, req: Request) -> bool:
        """One admit attempt; True when the replica accepted it."""
        frame = {"op": "admit", "rid": str(req.rid),
                 "prompt": [int(t) for t in req.prompt],
                 "max_new_tokens": req.max_new_tokens,
                 "arrival_ns": req.arrival_ns, "n": req.n_samples}
        if self.persist_sessions:
            sess = self._session_key.get(req.rid)
            if sess is not None:
                frame["session"] = str(sess)
        reply = h.call(frame)
        if reply.get("full"):
            return False
        if "error" in reply:
            raise RuntimeError(
                f"replica {h.idx} rejected request {req.rid}: {reply['error']}")
        req.state = RequestState.ACTIVE
        self.pending[h.idx][req.rid] = req
        # an n-way fan decodes n streams off one prefill — load it as such
        self.load[h.idx] += req.prompt_len + req.n_samples * req.max_new_tokens
        expected = self.affinity.score(req.prompt, [h.idx])[h.idx]
        self.affinity.publish(h.idx, req.prompt)
        session = self._session_key.get(req.rid)
        if session is not None:
            self.session_of[session] = h.idx
        self.stats["route_decisions"] += 1
        self.stats["expected_hit_tokens"] += expected
        self.stats["prompt_tokens"] += req.prompt_len
        self._emit(ev.EV_ROUTE_DECISION, h.task_id)
        self._emit(ev.EV_ROUTE_PREFIX_HITS, expected)
        return True

    def _dispatch(self):
        """Drain the global queue onto replicas.  A request no replica can
        take right now bounces to the queue front (original arrival_ns
        preserved — TTFT keeps counting) and dispatch stops: FIFO, a
        blocked head blocks the queue until a step frees capacity."""
        for _ in range(len(self.queue)):
            req = self.queue.pop()
            placed = False
            try:
                if self.disaggregate:
                    placed = self._dispatch_disaggregated(req)
                else:
                    for h in self._candidates(req):
                        try:
                            if self._admit_on(h, req):
                                placed = True
                                break
                        except ReplicaDead:
                            self._on_death(h)
            finally:
                if not placed:
                    self.queue.bounce(req)
                    self.stats["bounces"] += 1
            if not placed:
                break

    # ------------------------------------------------------------------
    # disaggregation
    # ------------------------------------------------------------------
    def _dispatch_disaggregated(self, req: Request) -> bool:
        """prefill -> export -> import -> decode-admit for one request.

        The prefill replica serves the prompt once (``max_new_tokens=1``
        retires at prefill; its single token is discarded — the decode
        replica regenerates it from the handed-off KV), then the full
        request is admitted on a decode replica with the ORIGINAL
        ``arrival_ns`` so decode-side TTFT spans the whole handoff."""
        prefills = [h for h in self.handles if h.alive and h.role == "prefill"]
        if not prefills:
            raise RuntimeError("all prefill replicas are dead")
        pf = min(prefills, key=lambda h: self.load[h.idx])
        prompt = [int(t) for t in req.prompt]
        try:
            reply = pf.call({"op": "admit", "rid": f"p{req.rid}",
                             "prompt": prompt, "max_new_tokens": 1,
                             "arrival_ns": req.arrival_ns})
            if reply.get("full"):
                return False
            pf.call({"op": "step"})  # drains the prefill wave
            spill = self.trace_dir / f"kv_{req.rid}.npz"
            exp = pf.call({"op": "export", "tokens": prompt,
                           "path": str(spill), "wire": self.wire_dtype})
        except ReplicaDead:
            self._on_death(pf)
            return False
        for h in self._candidates(req):
            try:
                if not exp.get("empty"):
                    imp = h.call({"op": "import", "path": str(spill)})
                    xfer_us = int(exp["us"]) + int(imp["us"])
                    self.stats["kv_xfers"] += 1
                    self.stats["kv_xfer_bytes"] += int(exp["bytes"])
                    self.stats["kv_xfer_us"] += xfer_us
                    self._emit(ev.EV_KV_XFER_BYTES, int(exp["bytes"]))
                    self._emit(ev.EV_KV_XFER_US, xfer_us)
                    self.affinity.publish_hashes(h.idx, exp["hashes"])
                if self._admit_on(h, req):
                    spill.unlink(missing_ok=True)
                    return True
            except ReplicaDead:
                self._on_death(h)
        return False

    # ------------------------------------------------------------------
    # the step
    # ------------------------------------------------------------------
    def _on_death(self, h: ReplicaHandle):
        """Bury a replica: drop its affinity/sticky state and bounce its
        in-flight requests to the survivors via the global queue."""
        if not h.alive:
            return
        h.kill()
        self.stats["deaths"] += 1
        self.affinity.drop_replica(h.idx)
        self.session_of = {k: v for k, v in self.session_of.items()
                           if v != h.idx}
        for req in self.pending[h.idx].values():
            self.queue.bounce(req)
            self.stats["bounces"] += 1
        self.pending[h.idx] = {}
        self.load[h.idx] = 0

    def _collect(self) -> dict[int, np.ndarray]:
        """Broadcast ``step`` to every busy replica, then fold replies.
        The broadcast-then-collect split is the concurrency: while the
        router blocks reading replica 0's reply, replicas 1..N-1 are
        computing their own waves."""
        busy = [h for h in self.handles if h.alive and self.pending[h.idx]]
        for h in busy:
            try:
                h.send({"op": "step"})
            except ReplicaDead:
                self._on_death(h)
        out: dict[int, np.ndarray] = {}
        for h in busy:
            if not h.alive:
                continue
            try:
                reply = h.recv()
            except ReplicaDead:
                self._on_death(h)
                continue
            for grid_s, info in reply.get("done", {}).items():
                grid = int(grid_s)
                req = self.pending[h.idx].pop(grid, None)
                if req is None:
                    continue
                req.tokens = list(info["tokens"])
                req.state = RequestState.DONE
                self.load[h.idx] -= (req.prompt_len
                                     + req.n_samples * req.max_new_tokens)
                self.stats["prefix_hit_tokens"] += info["prefix_hit_tokens"]
                info["replica"] = h.idx
                self.request_info[grid] = info
                out[grid] = np.asarray(info["tokens"], np.int32)
        self.results.update(out)
        return out

    def step(self) -> dict[int, np.ndarray]:
        """One dispatch + compute + collect round; returns the requests
        completed by THIS round as {global rid: np.ndarray tokens}."""
        self._dispatch()
        return self._collect()

    def run(self) -> dict[int, np.ndarray]:
        """Serve until the queue and every replica drain.  Returns all
        results accumulated so far (global rid -> tokens)."""
        idle = 0
        while self.queue or any(self.pending[h.idx] for h in self.handles
                                if h.alive):
            if not self._alive():
                raise RuntimeError("all serving replicas are dead with work "
                                   "outstanding")
            progressed = bool(self.step())
            idle = 0 if progressed else idle + 1
            if idle > 100:
                raise RuntimeError(
                    f"router stalled: {len(self.queue)} queued, "
                    f"{sum(len(p) for p in self.pending)} pending")
        return dict(self.results)

    # ------------------------------------------------------------------
    # maintenance / teardown
    # ------------------------------------------------------------------
    def sync_residency(self):
        """Refresh the affinity sets from worker-reported resident hashes
        (optimistic publishes go stale under eviction pressure)."""
        for h in self._alive(roles=("unified", "decode", "prefill")):
            try:
                self.affinity.reset_hashes(h.idx, h.call({"op": "stats"})
                                           ["resident"])
            except ReplicaDead:
                self._on_death(h)

    def kill_replica(self, idx: int):
        """Hard-kill one replica (failure injection for tests)."""
        self._on_death(self.handles[idx])

    def close(self, out_base=None) -> dict | None:
        """Shut the fleet down; with tracing, merge the router stream +
        every replica's segment files into one ``.prv`` at ``out_base``.
        Returns the write_prv path dict (or None untraced)."""
        segments: list[pathlib.Path] = []
        alive = [h for h in self.handles if h.alive]
        for h in alive:
            try:
                h.send({"op": "shutdown"})
            except ReplicaDead:
                self._on_death(h)
        for h in alive:
            if not h.alive:
                continue
            try:
                reply = h.recv()
                h.stats = {"stats": reply.get("stats", {}),
                           "pool": reply.get("pool", {})}
                h.segments = reply.get("segments", [])
                segments.extend(pathlib.Path(s) for s in h.segments)
            except ReplicaDead:
                pass
            h.alive = False
            h.proc.stdin.close()
            h.proc.wait()
        paths = None
        if self.tracer is not None:
            from repro.core.paraver import write_prv

            self.trace = self.tracer.finish()
            if out_base is not None:
                pathlib.Path(out_base).parent.mkdir(parents=True,
                                                    exist_ok=True)
                paths = write_prv(self.trace, out_base,
                                  segments=segments or None)
        if self._own_trace_dir and self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        return paths

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        for h in self.handles:
            if h.alive:
                try:
                    self.close()
                except Exception:
                    pass
                break
        for h in self.handles:
            if h.proc.poll() is None:
                h.kill()
        return False
