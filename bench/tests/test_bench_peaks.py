"""The peak table and the refusal of anything but a listed TPU."""
import jax
import pytest

from bench import device


def test_known_kind_has_its_peaks():
    p = device.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5e ", ""])
def test_unknown_kind_is_refused(kind):
    with pytest.raises(device.NoChip):
        device.peaks(kind)


def test_cpu_backend_is_refused():
    with pytest.raises(device.NoChip, match="needs a TPU"):
        device.require_chip(jax, 1)


def test_run_exits_2_without_a_chip(capsys):
    from bench import run

    assert run.main(["--workload", "kron20.bfs", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""
