"""Faults planted in the timed path, to show that the check refuses it.

Each ``plant_<name>(setattr)`` breaks the service underneath the harness
through ``setattr`` (pytest's ``monkeypatch.setattr`` in the tests, the
builtin in ``chipbench/control.py`` on the chip)."""

from __future__ import annotations


def plant_unchanged(setattr):
    """The fused update returns the fleet state it was given."""
    from repro.ps import engine

    setattr(engine, "_fused_state_update",
            lambda state, gs, counts, **kw: state)


def plant_half_batch(setattr):
    """Half of every pushed gradient is left out of the update."""
    from repro.ps import engine

    orig = engine._fused_state_update

    def half(state, gs, counts, **kw):
        gs = tuple(g.at[g.shape[-1] // 2:].set(0.0) for g in gs)
        return orig(state, gs, counts, **kw)

    setattr(engine, "_fused_state_update", half)


def plant_altered_answer(setattr):
    """One parameter of every pull is off by 0.01 where the pull makes it."""
    from repro.ps import engine

    orig = engine.ShardedTickEngine.pull

    def pull(self, job_id, since_version=None):
        out = dict(orig(self, job_id, since_version))
        k = sorted(out)[0]
        out[k] = out[k].at[0].add(0.01)
        return out

    setattr(engine.ShardedTickEngine, "pull", pull)


FAULTS = {"unchanged": plant_unchanged, "half_batch": plant_half_batch,
          "altered_answer": plant_altered_answer}
