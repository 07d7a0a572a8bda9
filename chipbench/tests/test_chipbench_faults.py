"""The correctness check refuses a broken timed path.

The run is driven as the command drives it, minus the look for a chip,
with the service broken underneath (``chipbench/faults.py``): the check
has to come out false for each fault the cells can have, and for the
control, the reference computed in bfloat16 in the program's place.  One
chip holds every cell, so no exchange between chips can be left out."""

import os
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.dirname(os.path.abspath(__file__))]

from chipbench import control, faults  # noqa: E402
from chipbench import harness  # noqa: E402
from tiny_cells import SEED, bench, run_tiny, tiny  # noqa: E402

CELL = "awdlm4-2s2w.saturate"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_check_refuses_a_broken_timed_path(monkeypatch, fault):
    faults.FAULTS[fault](monkeypatch.setattr)
    out = run_tiny(CELL, 0.3)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_control_fails_the_check():
    out = control.control_run(CELL, SEED, 0.3, "bf16_reference",
                              bench=bench(), cfg=tiny(CELL),
                              require_tpu=False, log=lambda *a: None)
    assert out["correct"] is False
    assert out["attempted"] > 0 and out["failed"] == 0
    for name, c in out["checks"].items():
        assert c["value"] > c["limit"], (name, c)


@pytest.mark.parametrize("change", [
    {"extra_key": 1},
    {"optimizer": {"kind": "adam", "dtype": "float32", "weight_decay": 0.01,
                   "lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}},
    {"worker_pushes": 2},
    {"tree_placement": "by_tensor"},
])
def test_configuration_keys_not_applied_are_refused(change):
    cfg = dict(tiny(CELL), **change)
    with pytest.raises(ValueError):
        harness.validate(cfg)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("steps", [1, 24, 25])
def test_replay_by_tensor_equals_the_whole_tree(dtype, steps):
    """Adam is elementwise: each tensor replayed alone gives, bit for bit,
    what the replay of the whole tree gives, in the reference's precision
    and in the control's."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference

    inv = [("a", 1000), ("b", 37), ("c", 4099), ("d", 1)]
    opt = tiny(CELL)["optimizer"]
    params, grads = (reference.tree_maker(inv, scale)
                     for scale in (reference.PARAM_SCALE,
                                   reference.GRAD_SCALE))
    init = params(reference.tree_key(SEED, 1, 2))
    gs = tuple(grads(reference.tree_key(SEED, 1, w)) for w in (0, 1))
    whole = reference.replay(opt, init, gs, steps, jnp.dtype(dtype))
    for name in init:
        one = reference.replay(opt, init[name], tuple(g[name] for g in gs),
                               steps, jnp.dtype(dtype))
        assert np.array_equal(one, whole[name]), name
    assert reference.gap(whole, whole, init) == 0.0


def test_reference_is_exact_against_itself():
    """The number compared reads 0 on identical trees, and otherwise the
    widest gap over the farthest the reference moved a parameter."""
    from chipbench import reference

    init = {"a": jnp.array([1.0, -1.0]), "b": jnp.array([3.0])}
    ref = {"a": jnp.array([1.0, -2.0]), "b": jnp.array([4.0])}
    assert reference.gap(ref, ref, init) == 0.0
    off = {"a": jnp.array([1.0, -2.0]), "b": jnp.array([4.1])}
    assert reference.gap(off, ref, init) == pytest.approx(0.1)
