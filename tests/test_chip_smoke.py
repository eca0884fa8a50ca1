"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny deployment.

The smoke script runs on the chip only; here its phase functions run at
N=16, M=50 with the Pallas kernel in the interpreter (``tests/conftest.py``
sets ``REPRO_PALLAS_INTERPRET=1``), so the script's paths and arguments stay
under the test suite. Only the TPU check is skipped, and it has a test of
its own: on the CPU ``main()`` refuses to run.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def test_phases_at_tiny_deployment(smoke, capsys):
    out = smoke.run(smoke.Deployment(n_ports=16, n_coflows=50))
    kernel, served, sweep = out["kernel"], out["served"], out["sweep"]
    assert kernel["flows"] > 0
    assert kernel["diverged"] <= kernel["allowed"]
    assert abs(served["wcct_ratio"] - 1.0) <= smoke.WCCT_TOLERANCE
    assert sweep["rows"] == 3
    printed = capsys.readouterr().out
    assert "hit=True" in printed
    assert "kernel: interpreter" in printed


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main() != 0
    captured = capsys.readouterr()
    assert "no TPU found" in captured.err
    assert '"ok"' not in captured.out  # no result line
