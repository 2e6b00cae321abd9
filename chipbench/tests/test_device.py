"""The measurement path refuses a CPU device and prints no result."""

import pytest

from chipbench import harness


def test_check_devices_refuses_the_cpu():
    import jax
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(harness.NoChip):
        harness.check_devices(1)


def test_main_exits_2_without_a_result(capsys):
    from chipbench.bench import Bench
    cell = Bench().spec["workloads"][0]["name"]
    rc = harness.main(["--workload", cell, "--seed",
                       str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"])
    assert rc == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "nothing was measured" in captured.err


def test_unknown_device_kind_is_an_error():
    from chipbench.bench import Bench, UnknownDevice
    with pytest.raises(UnknownDevice):
        Bench().peaks("cpu")
    assert Bench().peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
