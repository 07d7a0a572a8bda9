"""One cell of the benchmark: the service driven by one traffic mix.

Everything goes through the entry points a user calls::

    ShardedServiceRuntime(ParameterService()) -> add_job / remove_job
    -> attach_engine() (default fused fleet tick)
    -> submit_push -> tick -> pull, once the push future is done

The traffic is data (``chipbench/traffic/<name>.json``) read by the one
loop here; the deployment is data too (``chipbench/configs/<name>.json``).
Each tenant is a closed loop: it computes (or not), pushes one of its two
gradient trees in turn, waits for its update, pulls, and starts again.
An iteration is timed from when its push was due to when its pulled
parameters are ready on the device.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import itertools
import math
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import reference

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# The keys a configuration file may hold; every one is applied or
# checked here, and any other key is refused.
CONFIG_KEYS = {"name", "source", "deployment", "optimizer", "agg_throughput",
               "block", "push_compression", "worker_pushes", "grad_trees",
               "tenants", "reduced", "assumed", "models"}
TENANT_KEYS = {"name", "model", "servers", "workers", "iteration_s"}
# The one optimizer the service applies: fp32 Adam without weight decay.
OPTIMIZER = {"kind": "adam", "dtype": "float32", "weight_decay": 0.0}


def validate(cfg: dict):
    """Refuse a configuration that holds a key or a value this harness
    does not apply."""
    extra = set(cfg) - CONFIG_KEYS
    if extra:
        raise ValueError(f"configuration keys not applied: {sorted(extra)}")
    for spec in cfg["tenants"]:
        if set(spec) != TENANT_KEYS:
            raise ValueError(f"tenant keys {sorted(spec)}; "
                             f"expected {sorted(TENANT_KEYS)}")
    opt = cfg["optimizer"]
    fixed = {k: opt.get(k) for k in OPTIMIZER}
    if fixed != OPTIMIZER or set(opt) != set(OPTIMIZER) | {"lr", "b1", "b2",
                                                          "eps"}:
        raise ValueError(f"optimizer {opt}: the service applies "
                         f"{OPTIMIZER} with lr, b1, b2 and eps")
    if cfg["worker_pushes"] != 1:
        raise ValueError("one push per step carries the workers' summed "
                         "gradient: worker_pushes must be 1")
    if cfg["grad_trees"] not in (1, 2):
        raise ValueError("grad_trees must be 1 or 2")


def engine_spans(on: bool) -> None:
    """Turn the engine's own ``ps.*`` spans on or off, where the program
    under test has them."""
    try:
        from repro.ps import spans
    except ImportError:
        return
    spans.enable(on)


def _loss(params, batch):
    """The runtime asks each job for a loss; no tenant computes one here."""
    raise NotImplementedError("tenants of the benchmark push gradients")


@dataclass
class Tenant:
    index: int
    name: str
    model: str
    iteration_s: float
    servers: int
    workers: int
    factors: Any  # endless iterator of compute-time factors
    grads: tuple = ()  # gradient trees pushed in turn
    resident: bool = False
    arrival: int = -1  # arrivals so far, minus one
    steps: int = 0  # updates in the current residency
    phase: str = "off"  # "compute" | "inflight" | "off"
    due: float = math.inf
    submitted: float = 0.0
    fut: Any = None
    last_pull: Any = None
    # the pull after update ``check_step`` of this residency, on the
    # device until the tenant pushes again, and then on the host
    check_pull: Any = None
    check_host: Any = None
    exit_pending: bool = False


@dataclass
class Run:
    """What one run recorded; the metric readers take their numbers from
    it.  Times are ``time.perf_counter()`` seconds."""

    cell: str
    config: dict
    traffic: dict
    seconds: float
    device_kind: str = ""
    setup_s: float = 0.0
    t0: float = 0.0  # window opens
    t_end: float = 0.0  # no push is due after this
    t_close: float = 0.0  # the last update of the window is pulled back
    # (tenant, due, submitted, ready) of every iteration whose push was
    # submitted inside the window
    iters: List[tuple] = field(default_factory=list)
    # (start, end, real parameters applied) of each tick in the window
    ticks: List[tuple] = field(default_factory=list)
    # one dict per replan in the window: kind, start, host_s,
    # relayout_bytes, stall_s
    replans: List[dict] = field(default_factory=list)
    spans: List[tuple] = field(default_factory=list)  # (name, start, end)
    compiles: int = 0  # programs lowered in the window (compiled or loaded)
    compiled: List[tuple] = field(default_factory=list)  # (name, seconds)
    cache_misses: int = 0  # of those, compiled anew
    counters: Dict[str, Any] = field(default_factory=dict)
    memory_peak_bytes: Optional[int] = None  # of the fullest chip
    memory_peak_bytes_by_chip: List[Optional[int]] = field(
        default_factory=list)
    trace: Any = None  # chipbench.trace.Summary of a traced run
    setup_phases: Dict[str, float] = field(default_factory=dict)
    failed: int = 0


class Harness:
    """Set-up, window and check of one cell, in one process."""

    def __init__(self, cell: str, cfg: dict, traffic: dict, seed: int, *,
                 trace: bool = False):
        import jax

        validate(cfg)
        self.jax = jax
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.trace = trace
        self.opt = cfg["optimizer"]
        self.grad_trees = int(cfg["grad_trees"])
        self.check_step = int(traffic["check_step"])
        self.run = Run(cell=cell, config=cfg, traffic=traffic, seconds=0.0)
        self._compiles = [0, 0]
        self._lowered: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_plain_event)
        inv = {m: [(n, int(k)) for n, k in tensors]
               for m, tensors in cfg["models"].items()}
        self.makers = {m: (reference.tree_maker(v, reference.PARAM_SCALE),
                           reference.tree_maker(v, reference.GRAD_SCALE))
                       for m, v in inv.items()}
        self.sizes = {m: sum(n for _, n in v) for m, v in inv.items()}
        lo, hi = traffic.get("jitter", (1.0, 1.0))
        n_f = int(traffic.get("jitter_values", 1))
        base = np.linspace(lo, hi, n_f) if n_f > 1 else np.ones(1)
        compute = traffic["compute"] == "iteration"
        self.tenants: List[Tenant] = []
        for i, spec in enumerate(cfg["tenants"]):
            order = np.random.default_rng([self.seed, i]).permutation(base)
            scale = float(spec["iteration_s"]) if compute else 0.0
            self.tenants.append(Tenant(
                index=i, name=spec["name"], model=spec["model"],
                iteration_s=float(spec["iteration_s"]),
                servers=int(spec["servers"]), workers=int(spec["workers"]),
                factors=itertools.cycle([scale * float(f) for f in order])))
        churn = traffic.get("churn")
        self.churner = (self.tenants[int(churn["tenant_index"])]
                        if churn else None)
        # (tenant, arrival, steps, last pull, check pull) of a residency
        # that ended
        self.finished: List[tuple] = []
        self.svc = self.rt = self.eng = None

    # ------------------------------------------------------------ plumbing
    def _on_event(self, event, duration_secs=None, **kw):
        if event == COMPILE_EVENT:
            self._compiles[0] += 1
            self._lowered.append((kw.get("fun_name", "?"), duration_secs))

    def _on_plain_event(self, event, **kw):
        if event == CACHE_MISS_EVENT:
            self._compiles[1] += 1

    def close(self):
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self._on_event)
        monitoring.unregister_event_listener(self._on_plain_event)

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        if self.trace:
            with self.jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.run.spans.append((name, t0, time.perf_counter()))

    # ------------------------------------------------------------- tenants
    def _params(self, t: Tenant):
        return self.makers[t.model][0](
            reference.tree_key(self.seed, t.index, 2 + t.arrival))

    def _grads(self, t: Tenant):
        """The tenant's gradient trees, pushed in turn (one or two)."""
        mk = self.makers[t.model][1]
        return tuple(mk(reference.tree_key(self.seed, t.index, w))
                     for w in range(self.grad_trees))

    def arrive(self, t: Tenant) -> tuple:
        """Register ``t`` with fresh parameters; returns when the
        ``add_job`` call (the replan) started and its host seconds."""
        t.arrival += 1
        params = self._params(t)
        self.jax.block_until_ready(params)
        with self.span("replan"):
            t0 = time.perf_counter()
            self.rt.add_job(
                t.name, params, _loss, iteration_duration=t.iteration_s,
                n_workers=t.workers, required_servers=t.servers,
                agg_throughput=float(self.cfg["agg_throughput"]),
                lr=self.opt["lr"], b1=self.opt["b1"], b2=self.opt["b2"],
                eps=self.opt["eps"],
                push_compression=self.cfg["push_compression"])
            host = time.perf_counter() - t0
        del params
        t.resident, t.steps, t.last_pull, t.check_host = True, 0, None, None
        t.phase, t.due = "compute", time.perf_counter() + next(t.factors)
        return t0, host

    def leave(self, t: Tenant) -> float:
        assert t.phase == "compute", t.phase
        if t.last_pull is not None:
            self._to_host(t)
            self.finished = [(t, t.arrival, t.steps, t.last_pull,
                              t.check_host)]
        t.last_pull = t.check_host = None
        with self.span("replan"):
            t0 = time.perf_counter()
            self.rt.remove_job(t.name)
            host = time.perf_counter() - t0
        t.resident, t.phase, t.exit_pending = False, "off", False
        t.due = math.inf
        return host

    def _push(self, t: Tenant, now: float):
        # The last pull is dropped here, not kept beside the tick: the
        # service's HBM has no room for every tenant's pull besides.
        t.last_pull = None
        if t.check_pull is not None:
            self._to_host(t)
        with self.span("client.push"):
            t.fut = self.eng.submit_push(t.name,
                                         t.grads[t.steps % len(t.grads)])
        t.submitted, t.phase = now, "inflight"

    def _pull(self, t: Tenant) -> float:
        """Pull ``t``'s parameters once its update is done; the pull is
        kept for the check until the tenant pushes again."""
        with self.span("engine.pull"):
            pulled = self.eng.pull(t.name)
        with self.span("client.wait"):
            self.jax.block_until_ready(pulled)
        ready = time.perf_counter()
        t.steps += 1
        t.phase, t.due = "compute", ready + next(t.factors)
        t.last_pull = pulled
        if t.steps == self.check_step:
            for x in self.jax.tree_util.tree_leaves(pulled):
                x.copy_to_host_async()
            t.check_pull = pulled
        return ready

    def _to_host(self, t: Tenant):
        """Move the check pull to the host (its copy started when it was
        pulled), so that it holds no device memory."""
        if t.check_pull is not None:
            t.check_host = self.jax.device_get(t.check_pull)
            t.check_pull = None

    def _tick(self, inflight) -> tuple:
        with self.span("engine.tick"):
            t0 = time.perf_counter()
            self.eng.tick()
            t1 = time.perf_counter()
        applied = sum(self.sizes[t.model] for t in inflight if t.fut.done())
        return t0, t1, applied

    # --------------------------------------------------------------- set-up
    def setup(self):
        from repro.core import ParameterService
        from repro.ps.service_runtime import ShardedServiceRuntime

        phases, t0 = self.run.setup_phases, time.perf_counter()
        self.svc = ParameterService(plan_pad_to=int(self.cfg["block"]))
        self.rt = ShardedServiceRuntime(self.svc)
        self.eng = self.rt.attach_engine()
        for t in self.tenants:
            t.grads = self._grads(t)
        self.jax.block_until_ready([t.grads for t in self.tenants])
        phases["gradients"], t0 = time.perf_counter() - t0, time.perf_counter()
        for t in self.tenants:
            if t is not self.churner:
                self.arrive(t)
        phases["add_jobs"], t0 = time.perf_counter() - t0, time.perf_counter()
        rounds = int(self.traffic.get("warm_rounds", 1))
        self._warm(rounds)
        if self.churner is not None:
            # Both plans' programs: one arrival and one exit.
            self.arrive(self.churner)
            self._warm(rounds)
            self.leave(self.churner)
            self._warm(rounds)
        self.jax.block_until_ready([st["flat"]
                                    for st in self.rt.states.values()])
        phases["warm"] = time.perf_counter() - t0
        if any(t.steps >= self.check_step for t in self.tenants):
            raise ValueError("check_step has to lie beyond the warm phase, "
                             "in the window")

    def _warm(self, rounds: int):
        """Run every pending pattern the window can meet: all tenants
        together, or every non-empty subset of them when cadences differ."""
        live = [t for t in self.tenants if t.resident]
        if self.traffic["warm"] == "subsets":
            groups = [list(c) for k in range(1, len(live) + 1)
                      for c in itertools.combinations(live, k)]
        else:
            groups = [live]
        for _ in range(rounds):
            for group in groups:
                for t in group:
                    self._push(t, time.perf_counter())
                while not all(t.fut.done() for t in group):
                    self._tick(group)
                for t in group:
                    self._pull(t)
        for t in live:
            t.due = time.perf_counter() + next(t.factors)
        self.run.spans.clear()

    # --------------------------------------------------------------- window
    def window(self, seconds: float, t_setup0: float):
        """Measure for ``seconds``; every push due inside it is served and
        pulled back before this returns."""
        run, clock = self.run, time.perf_counter
        run.seconds = float(seconds)
        stats0 = self._int_stats()
        self._compiles[:] = [0, 0]
        self._lowered.clear()
        run.t0 = t0 = clock()
        run.setup_s = t0 - t_setup0
        run.t_end = t_end = t0 + seconds
        for t in self.tenants:
            if t.resident:
                t.due = t0 + next(t.factors)
        churn = self.traffic.get("churn")
        next_event = t0 + float(churn["first_s"]) if churn else math.inf
        idle = self.traffic["compute"] == "none"
        trackers = []  # open replans: (record, start, pending names)

        def needed(t):
            return any(t.name in pend for _, _, pend in trackers)

        def open_replan(kind, start, host):
            rec = dict(kind=kind, start=start, host_s=host,
                       relayout_bytes=int(self.rt.last_relayout_bytes),
                       stall_s=None)
            run.replans.append(rec)
            trackers.append((rec, start, {t.name for t in self.tenants
                                          if t.resident}))

        def served(t, ready):
            for tr in list(trackers):
                rec, start, pend = tr
                if t.submitted >= start and t.name in pend:
                    pend.discard(t.name)
                    if not pend:
                        rec["stall_s"] = ready - start
                        trackers.remove(tr)

        def churn_leave(t):
            start = clock()
            host = self.leave(t)
            for _, _, pend in trackers:
                pend.discard(t.name)
            for tr in list(trackers):
                if not tr[2]:
                    tr[0]["stall_s"] = clock() - tr[1]
                    trackers.remove(tr)
            open_replan("exit", start, host)

        while True:
            now = clock()
            if now >= next_event and next_event < t_end:
                ct = self.churner
                next_event += float(churn["every_s"])
                if not ct.resident:
                    open_replan("arrival", *self.arrive(ct))
                elif ct.phase == "compute":
                    churn_leave(ct)
                else:
                    ct.exit_pending = True
                continue
            live = [t for t in self.tenants if t.resident]
            for t in live:
                # With no compute time every push of a round falls due
                # together; judge the round by the clock, so the window
                # ends on whole rounds and meets no pending pattern that
                # the warm-up did not.
                in_window = (now if idle else t.due) < t_end
                if (t.phase == "compute" and t.due <= now
                        and (in_window or needed(t))):
                    self._push(t, clock())
            inflight = [t for t in live if t.phase == "inflight"]
            if inflight:
                tick = self._tick(inflight)
                if tick[2]:
                    run.ticks.append(tick)
                for t in inflight:
                    if t.fut.cancelled():
                        run.failed += 1
                        t.phase, t.due = "compute", clock()
                    elif t.fut.done():
                        due = t.due
                        ready = self._pull(t)
                        run.iters.append((t.name, due, t.submitted, ready))
                        served(t, ready)
                        if t.exit_pending:
                            churn_leave(t)
                continue
            waits = [t.due for t in live if t.phase == "compute"
                     and ((now if idle else t.due) < t_end or needed(t))]
            if next_event < t_end:
                waits.append(next_event)
            if not waits:
                break
            with self.span("client.compute"):
                time.sleep(max(0.0, min(waits) - clock()))
        run.t_close = clock()
        run.compiles, run.cache_misses = self._compiles
        run.compiled = list(self._lowered)
        stats = self.eng.stats
        run.counters = dict(
            n_ticks=stats.n_ticks, n_launches=stats.n_launches,
            n_applied=stats.n_applied,
            n_fleet_fallbacks=stats.n_fleet_fallbacks,
            n_rollbacks=stats.n_rollbacks, n_quarantines=stats.n_quarantines,
            n_snapshots=stats.n_snapshots, n_replans=self.rt.n_replans,
            n_shards=self.rt.n_shards,
            lanes=int(self.rt.splan.total_len) if self.rt.splan else 0,
            window={k: v - stats0[k] for k, v in self._int_stats().items()})

    def _int_stats(self) -> Dict[str, int]:
        """Every integer counter of the engine's ``TickStats``, so that a
        counter the engine gains reaches the readers with no edit here."""
        stats = self.eng.stats
        values = {f.name: getattr(stats, f.name)
                  for f in dataclasses.fields(stats)}
        return {k: v for k, v in values.items() if isinstance(v, int)}

    # ---------------------------------------------------------------- check
    def release(self):
        """Keep each tenant's last pull and check pull; free the service
        and the pushes."""
        keep = list(self.finished)
        for t in self.tenants:
            if t.resident and t.steps:
                keep.append((t, t.arrival, t.steps, t.last_pull,
                             t.check_host if t.check_pull is None
                             else t.check_pull))
        for t in self.tenants:
            t.grads, t.last_pull, t.fut = (), None, None
            t.check_pull = t.check_host = None
        self.finished = []
        self.svc = self.rt = self.eng = None
        gc.collect()
        return keep

    def check(self, kept, control=None):
        """The numbers compared, ``{"gap.<tenant>": (value, steps)}``: the
        gap of the tenant's last pull from the reference replay
        (``gap``), and of its pull after update ``check_step``
        (``gap_at_k``; a residency that ended before that step gives its
        last pull).  With ``control`` (a dtype) the reference computed in
        that precision takes the pulls' place.

        Each tensor is replayed alone, on the chip its trees were made on,
        with its pulled leaf moved there, and the maxima are reduced on
        the host: the replay holds the moments of one tensor."""
        import jax
        import jax.numpy as jnp

        gaps = {}
        for t, arrival, steps, pulled, at_k in kept:
            init = self.makers[t.model][0](
                reference.tree_key(self.seed, t.index, 2 + arrival))
            grads = self._grads(t)
            k = self.check_step if at_k is not None else steps
            checks = (("gap_at_k", k, pulled if at_k is None else at_k),
                      ("gap", steps, pulled))
            parts = {name: [] for name, _, _ in checks}
            for tensor, p in init.items():
                gs = tuple(g[tensor] for g in grads)
                for name, n, got in checks:
                    ref = reference.replay(self.opt, p, gs, n)
                    if control is not None:
                        mine = reference.replay(self.opt, p, gs, n,
                                                jnp.dtype(control))
                    else:
                        mine = jax.device_put(got[tensor], p.device)
                    parts[name].append(reference.gap_parts(mine, ref, p))
                    del ref, mine
            del init, grads
            for name, n, _ in checks:
                gaps[f"{name}.{t.name}"] = (reference.gap_of(parts[name]), n)
        return gaps
