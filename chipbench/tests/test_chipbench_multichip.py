"""A cell on four chips, rehearsed on four virtual CPU devices.

The module's own command runs in a child process started with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (a process's device
count is fixed when JAX starts) and prints one JSON object:

- a tiny ``awdlm4-2s2w.saturate`` run through ``run_cell`` as a four-chip
  cell;
- where a tenant's trees lie, and the check of pulls returned spread over
  the four chips, right and with one parameter altered.
"""

import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "awdlm4-2s2w.saturate"
CHIPS = 4


def _spread_pulls_check(devs):
    """Gaps of a tenant whose pulls (the reference's own replay, as a sound
    program would return them) lie on chip i mod 4 for tensor i, as a
    program that places shard spaces per chip returns them, and of the
    same pulls with one parameter altered."""
    import jax

    from chipbench import harness, reference
    from chipbench import run as R
    from tiny_cells import SEED, bench, tiny

    _, _, _, traffic = R.cell_spec(bench(), CELL)
    h = harness.Harness(CELL, tiny(CELL), traffic, SEED)
    try:
        t = h.tenants[1]
        init = h.makers[t.model][0](reference.tree_key(SEED, t.index, 2))
        gs = h._grads(t)
        placed = {n: sorted(d.id for d in x.devices())
                  for n, x in init.items()}

        def pulls(steps):
            return {n: jax.device_put(reference.replay(
                h.opt, init[n], tuple(g[n] for g in gs), steps),
                devs[i % len(devs)]) for i, n in enumerate(init)}

        steps = h.check_step + 6
        last = pulls(steps)
        pulled = {n: sorted(d.id for d in x.devices())
                  for n, x in last.items()}
        right = h.check([(t, 0, steps, last, pulls(h.check_step))])
        name = list(last)[-1]
        last[name] = last[name].at[0].add(0.01)
        wrong = h.check([(t, 0, steps, last, pulls(h.check_step))])
    finally:
        h.close()
    return {"names": list(init), "placed": placed, "pulled": pulled,
            "right": {k: v for k, (v, _) in right.items()},
            "wrong": {k: v for k, (v, _) in wrong.items()}}


def main():
    import jax

    from chipbench import run as R
    from tiny_cells import SEED, bench, tiny

    devs = jax.devices()[:CHIPS]
    b = bench()
    for w in b["workloads"]:
        if w["name"] == CELL:
            w["chips"] = CHIPS
    cell = R.run_cell(CELL, SEED, 0.3, False, bench=b, require_tpu=False,
                      cfg=tiny(CELL), t_start=time.perf_counter(),
                      log=lambda *a: None)
    out = {"cell": cell, "spread": _spread_pulls_check(devs)}
    print(json.dumps(out), flush=True)


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          f" --xla_force_host_platform_device_count={CHIPS}"))
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT, os.path.join(ROOT, "src"), HERE,
         env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_chip_cell_is_correct_with_every_chip_read(four):
    cell = four["cell"]
    assert cell["correct"], cell["checks"]
    assert cell["attempted"] > 0 and cell["failed"] == 0
    assert cell["device"]["count"] == CHIPS
    # the CPU reports no memory; one entry per chip all the same
    assert cell["device"]["memory_peak_bytes_by_chip"] == [None] * CHIPS


def test_tenant_trees_lie_on_the_first_chip(four):
    got = four["spread"]
    assert got["placed"] == {n: [0] for n in got["names"]}
    assert got["pulled"] == {n: [i % CHIPS]
                             for i, n in enumerate(got["names"])}


def test_pulls_on_every_chip_are_checked_on_the_first(four):
    """Pulls returned on chip i mod 4 are moved to the reference's chip one
    tensor at a time: the reference's own replay reads 0, one altered
    parameter fails."""
    got = four["spread"]
    assert set(got["right"]) == {"gap.awd-lm-1", "gap_at_k.awd-lm-1"}
    assert all(v == 0.0 for v in got["right"].values())
    assert got["wrong"]["gap.awd-lm-1"] > 3e-3
    assert got["wrong"]["gap_at_k.awd-lm-1"] == 0.0


if __name__ == "__main__":
    main()
