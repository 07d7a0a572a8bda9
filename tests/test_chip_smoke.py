"""``chip_smoke.py --tiny`` in-process on the CPU: the script's control
flow (tenants, two replans, pulls against the numpy reference, counter
checks, the last JSON line) without a chip."""

import importlib.util
import json
import os

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tiny_rehearsal_passes_and_names_its_platform(capsys):
    smoke = _load_smoke()
    assert smoke.main(["--tiny"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    out = "\n".join(lines)
    assert out.count("relayout bytes") == 2
    assert "MISMATCH" not in out and "FAIL" not in out


def test_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    smoke = _load_smoke()
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""
