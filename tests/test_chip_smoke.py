"""The chip smoke's phases, rehearsed on the CPU at a tiny size.

`chip_smoke.run_smoke` is what the chip runs; here every one of its checks
runs with the Pallas kernels in interpret mode (n=2048: four 512-row
segments, the smallest that take the bulk builder), and `main()` must
refuse a host whose first JAX device is not a TPU.
"""

import importlib.util
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_pass_in_interpret_mode(chip_smoke):
    logs = []
    summary = chip_smoke.run_smoke(2048, requests=16, interpret=True,
                                   log=logs.append)
    assert summary["n"] == 2048
    assert set(summary["recall"]) == set(chip_smoke.P_RECALL)
    assert min(summary["recall"].values()) >= chip_smoke.RECALL_FLOOR
    assert set(summary["kernel_rel_err"]) == set(chip_smoke.P_KERNEL)
    assert max(summary["kernel_rel_err"].values()) <= chip_smoke.KERNEL_RTOL
    assert any("own nearest neighbour" in line for line in logs)


def test_smoke_refuses_a_host_without_tpu(chip_smoke, monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices(
        backend="cpu"))
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err

