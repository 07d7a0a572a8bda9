#!/usr/bin/env python3
"""Drive the shared-service data plane once on one TPU chip, at full width.

The deployment is the paper's §5.1 testbed at its published tensor
inventories (``repro.configs.paper_workloads.MODEL_TENSORS``), fp32
parameters made from ``--seed``: AlexNet, BERT-base and AWD-LSTM are
resident from the start; a second AlexNet arrives after a few ticks and
exits a few ticks later, so the fleet replans twice.  Everything goes
through the entry points a user calls:

    ParameterService -> ShardedServiceRuntime.add_job -> attach_engine()
    (default fused fleet tick) -> submit_push / tick / drain -> pull,
    with a relayout of the shard states on the arrival and on the exit.

After every phase each tenant's pull is compared with a plain fp32 numpy
reference -- a sequential per-job Adam on the unpacked trees that shares
no code with the service -- within ``max|pull - ref| <= RTOL * max|ref|``
over the tenant's parameters.
The run fails on a mismatch, on any fleet fallback, rollback or
quarantine, on a replan that moved no bytes, or (on the TPU) when the
Pallas kernel is missing from the fleet-tick or relayout program.

Usage (from the root of a checkout)::

    python chip_smoke.py            # one TPU chip, full width
    python chip_smoke.py --tiny     # small tenants on any backend

Wall times it prints are set-up times (compilation included), not
metrics.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Off the TPU,
without ``--tiny``, it exits non-zero and prints no result.  It runs in
one process and starts no other.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# (tenant, §5.1 model).  A second AlexNet stands in for VGG19: with VGG19
# resident, a v5e cannot load the four-tenant fleet-tick program (it
# reserves 5.18 GB with 4.09 GB free beside the state and its rollback
# snapshot).  The arriving tenant's id sorts before the resident
# AlexNet's, so sharing its Aggregator moves bytes.
RESIDENT = (("alexnet-1", "alexnet"), ("bert", "bert"),
            ("awd-lm", "awd-lm"))
ARRIVING = ("alexnet-0", "alexnet")
SERVERS, WORKERS = 2, 2  # the paper's (servers, workers) testbed setting
TICKS_PER_PHASE = 3
LR, B1, B2, EPS = 1e-3, 0.9, 0.999, 1e-8
PARAM_SCALE, GRAD_SCALE = 0.05, 0.01
# A tenant's pull matches the reference when max|pull - ref| <= RTOL *
# max|ref| over its parameters: relative to their scale, not elementwise,
# because an fp32 Adam step is only as exact as its bias correction
# 1 - b2**t, whose cancellation at small t leaves ~1e-7 absolute error in
# any fp32 implementation wherever a parameter passes through zero.
RTOL = 1e-5
# --tiny shrinks every tensor by this factor and the profiled aggregation
# throughput with it, so the control plane packs the tenants as at full
# width.
TINY_SHRINK = 4096


_say = functools.partial(print, flush=True)


def _inventory(model, shrink):
    from repro.configs.paper_workloads import MODEL_TENSORS

    return [(name, -(-n // shrink)) for name, n in MODEL_TENSORS[model]]


def _tree_maker(inventory, scale):
    """Jitted ``key -> {tensor: (n,) f32 normal * scale}`` for one tenant:
    one program per tenant, so seeding and every push compile once."""
    import jax
    import jax.numpy as jnp

    names = [name for name, _ in inventory]
    offs = [0]
    for _, n in inventory:
        offs.append(offs[-1] + n)

    @jax.jit
    def make(key):
        flat = jax.random.normal(key, (offs[-1],), jnp.float32) * scale
        return {name: flat[offs[i]:offs[i + 1]]
                for i, name in enumerate(names)}

    return make


def _l2_loss(params, batch):
    import jax
    import jax.numpy as jnp

    del batch
    return sum(jnp.sum(p * p) for p in jax.tree_util.tree_leaves(params))


class _Reference:
    """Textbook fp32 Adam per job, in numpy on the host."""

    def __init__(self, params):
        import numpy as np

        self.p = {k: np.array(v, np.float32) for k, v in params.items()}
        self.mu = {k: np.zeros_like(v) for k, v in self.p.items()}
        self.nu = {k: np.zeros_like(v) for k, v in self.p.items()}
        self.t = 0

    def step(self, grads):
        import numpy as np

        self.t += 1
        # fp32 throughout, the bias corrections included.
        t = np.float32(self.t)
        c1 = np.float32(1.0) - np.float32(B1) ** t
        c2 = np.float32(1.0) - np.float32(B2) ** t
        for k, g in grads.items():
            g = np.asarray(g, np.float32)
            mu, nu = self.mu[k], self.nu[k]
            mu *= B1
            mu += (1.0 - B1) * g
            nu *= B2
            nu += (1.0 - B2) * (g * g)
            self.p[k] -= LR * (mu / c1) / (np.sqrt(nu / c2) + EPS)


def _compare(pulled, ref):
    """(largest |pull - ref|, its ratio to the tolerance RTOL * max|ref|)."""
    import numpy as np

    max_abs = scale = 0.0
    for k, r in ref.p.items():
        a = np.asarray(pulled[k], np.float32).reshape(-1)
        max_abs = max(max_abs, float(np.abs(a - r).max()))
        scale = max(scale, float(np.abs(r).max()))
    return max_abs, max_abs / (RTOL * scale)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="small tenants on any backend (a rehearsal; "
                         "prints its real platform)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the parameters and gradients")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.tiny:
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"use --tiny for a rehearsal off the chip", file=sys.stderr)
        return 2

    from repro.configs.paper_workloads import (AGG_THROUGHPUT,
                                               ITERATION_DURATION)
    from repro.core import ParameterService
    from repro.kernels.relayout import kernel as relayout_kernel
    from repro.ps.elastic import compile_migration_delta
    from repro.ps.service_runtime import ShardedServiceRuntime

    shrink = TINY_SHRINK if args.tiny else 1
    on_tpu = dev.platform == "tpu"
    _say(f"device_kind: {dev.device_kind} (platform {dev.platform}, "
         f"{len(devices)} device(s)); tiny={args.tiny}")
    failures = []
    key = jax.random.PRNGKey(args.seed)
    tenants = dict(RESIDENT + (ARRIVING,))
    makers, index = {}, {}
    for i, (m, model) in enumerate(tenants.items()):
        inv = _inventory(model, shrink)
        makers[m] = (_tree_maker(inv, PARAM_SCALE),
                     _tree_maker(inv, GRAD_SCALE))
        index[m] = i
        _say(f"tenant {m} ({model}): {len(inv)} tensors, "
             f"{sum(n for _, n in inv)} fp32 parameters")

    svc = ParameterService()
    rt = ShardedServiceRuntime(svc)
    eng = rt.attach_engine()
    refs = {}
    kernel_seen = {"fleet tick": False, "relayout": False}

    def add(m):
        params = makers[m][0](jax.random.fold_in(key, index[m]))
        refs[m] = _Reference(params)
        rt.add_job(m, params, _l2_loss, iteration_duration=ITERATION_DURATION[
                       (tenants[m], SERVERS, WORKERS)],
                   n_workers=WORKERS, required_servers=SERVERS,
                   agg_throughput=AGG_THROUGHPUT / shrink, lr=LR)

    def check_pulls(phase):
        for m in rt.job_ids:
            max_abs, ratio = _compare(eng.pull(m), refs[m])
            verdict = "ok" if ratio <= 1.0 else "MISMATCH"
            _say(f"  pull {phase:>13} {m:>7}: step {refs[m].t}, "
                 f"max |pull-ref| {max_abs:.3e}, "
                 f"max err/tol {ratio:.3e} {verdict}")
            if ratio > 1.0:
                failures.append(f"{m} pull after {phase} off the reference")

    def tick_phase(phase, tick0):
        t0 = time.perf_counter()
        futures = []
        for t in range(TICKS_PER_PHASE):
            t1 = time.perf_counter()
            pieces = 0
            for m in rt.job_ids:
                grads = makers[m][1](jax.random.fold_in(
                    key, 1000 * (tick0 + t + 1) + index[m]))
                refs[m].step(grads)
                futures.append(eng.submit_push(m, grads))
                pieces += len(rt.splan.job_layout(m).shard_ids)
                del grads
            applied = eng.tick()
            _say(f"    tick {tick0 + t + 1}: {applied} pieces applied, wall "
                 f"{time.perf_counter() - t1:.3f} s (pushes and reference "
                 f"included)")
            if applied != pieces:
                failures.append(f"{phase}: a fleet tick applied {applied} "
                                f"of {pieces} pending pieces")
            if not kernel_seen["fleet tick"]:
                kernel_seen["fleet tick"] = any(
                    "tpu_custom_call" in exe.as_text()
                    for app in eng._fleet_appliers.values()
                    for exe in app._exes.values())
        eng.drain()
        if not all(f.done() for f in futures):
            failures.append(f"{phase}: a push future did not resolve")
        check_pulls(phase)
        _say(f"  {phase}: {TICKS_PER_PHASE} ticks, wall "
             f"{time.perf_counter() - t0:.3f} s (set-up time, "
             f"compilation included; not a metric)")

    def replan(phase, action):
        old, n_replans = rt.splan, rt.n_replans
        t0 = time.perf_counter()
        action()
        jax.block_until_ready([st["flat"] for st in rt.states.values()])
        wall = time.perf_counter() - t0
        _say(f"  {phase}: replan to {rt.n_shards} shard spaces "
             f"({rt.splan.total_len} lanes), relayout bytes "
             f"{rt.last_relayout_bytes}, cross-Aggregator bytes "
             f"{rt.last_migration_bytes}, touched "
             f"{list(rt.last_replan_touched)}, wall {wall:.3f} s "
             f"(set-up time, compilation included; not a metric)")
        if rt.n_replans != n_replans + 1 or rt.last_relayout_bytes <= 0:
            failures.append(f"{phase}: no replan that moved bytes")
        # The relayout runs eagerly; compile its kernel at this replan's
        # shapes to show what the chip executed.
        for sid in rt.splan.shard_ids:
            if sid not in old.shard_ids or old.shard_of(sid) == \
                    rt.splan.shard_of(sid):
                continue
            delta = compile_migration_delta(old.shard_of(sid),
                                            rt.splan.shard_of(sid))
            if not delta.touched_blocks.size:
                continue
            base = jax.ShapeDtypeStruct((delta.new_len,), np.float32)
            staged = jax.ShapeDtypeStruct(
                (delta.touched_blocks.size * delta.block,), np.float32)
            dst = jax.ShapeDtypeStruct(delta.touched_blocks.shape, np.int32)
            text = jax.jit(lambda b, s, d, _blk=delta.block:
                           relayout_kernel.relayout_scatter(
                               b, s, d, block=_blk,
                               interpret=not on_tpu)).lower(
                (base,) * 3, (staged,) * 3, dst).compile().as_text()
            kernel_seen["relayout"] |= "tpu_custom_call" in text
        check_pulls(phase)

    def memory(phase):
        stats = dev.memory_stats() or {}
        _say(f"  {phase}: peak_bytes_in_use "
             f"{stats.get('peak_bytes_in_use', 'n/a')} of bytes_limit "
             f"{stats.get('bytes_limit', 'n/a')}")

    t0 = time.perf_counter()
    for m, _ in RESIDENT:
        add(m)
    _say(f"phase seed: {len(RESIDENT)} resident tenants in "
         f"{rt.n_shards} shard spaces ({rt.splan.total_len} lanes, block "
         f"{rt.splan.shards[0].block_align}), wall "
         f"{time.perf_counter() - t0:.3f} s (set-up time; not a metric)")
    check_pulls("seed")
    memory("seed")
    tick_phase("resident", 0)
    memory("resident")
    replan("arrival", lambda: add(ARRIVING[0]))
    tick_phase("with arrival", TICKS_PER_PHASE)
    memory("with arrival")
    replan("exit", lambda: rt.remove_job(ARRIVING[0]))
    refs.pop(ARRIVING[0])
    tick_phase("after exit", 2 * TICKS_PER_PHASE)
    memory("after exit")

    stats = dataclasses.asdict(eng.stats)
    _say("fleet TickStats: " + json.dumps(stats, sort_keys=True))
    for name in ("n_fleet_fallbacks", "n_rollbacks", "n_quarantines"):
        if stats[name]:
            failures.append(f"{name} = {stats[name]}")
    for what, seen in kernel_seen.items():
        _say(f"tpu_custom_call in the {what} program: {seen}")
        if on_tpu and not seen:
            failures.append(f"no Pallas kernel in the {what} program")
    if failures:
        for f in failures:
            _say(f"FAIL: {f}")
        return 1
    _say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache(ROOT)
    sys.exit(main())
