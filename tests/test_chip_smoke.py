"""chip_smoke.py off the chip: it refuses the CPU unless told it is a
rehearsal, and the rehearsal (interpret mode) passes every phase."""
import json

import jax
import pytest

import chip_smoke


def test_refuses_to_run_without_a_tpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert out.splitlines()[0].startswith("device: platform=")


def test_cpu_rehearsal_serves_every_phase(capsys):
    assert chip_smoke.main(["--allow-cpu", "--seed", "1"]) == 0
    lines = capsys.readouterr().out.splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == jax.devices()[0].platform
    for phase in ("paper@64", "model@64", "yi-9b"):
        assert f"[{phase}] PASS" in lines
