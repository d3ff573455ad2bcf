"""Shared set-up of the benchmark's own tests: import paths, and cells
cut to a size the CPU runs in seconds (the chip runs them full size)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for path in (str(ROOT / "src"), str(ROOT)):
    if path not in sys.path:
        sys.path.insert(0, path)

#: configuration keys overridden for CPU runs, per configuration
SMALL = {"graph500-kron20": {"scale": 9}, "dimacs10-rgg20": {"scale": 10}}
SMALL_SCHEDULER = {"num_workers": 64, "work_budget": 1024, "max_rounds": 256}


@pytest.fixture
def small_cell(tmp_path, monkeypatch):
    """``make(name)``: the cell ``name`` at a CPU size; the harness's look
    for a chip passes the CPU (with a v5e's peaks), and the compile cache
    goes to a temporary directory."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    from bench import device, run

    def any_device(jax, chips):
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}

    v5e = device.peaks("TPU v5 lite")
    monkeypatch.setattr(device, "require_chip", any_device)
    monkeypatch.setattr(device, "peaks", lambda kind: v5e)

    def make(name):
        cell = run.load_cell(name)
        cell.config.update(SMALL[cell.config["name"]])
        cell.config["scheduler"] = dict(cell.config["scheduler"],
                                        **SMALL_SCHEDULER)
        return cell

    return make
