"""The harness measures a TPU or nothing."""
from types import SimpleNamespace

import pytest

from bench.harness.device import NoChip, peaks_for, require_tpu


def test_peaks_of_a_v5e_come_from_the_table():
    p = peaks_for("TPU v5 lite")
    assert (p.bf16_flops_per_s, p.int8_ops_per_s, p.hbm_bytes_per_s) == (197e12, 393e12, 819e9)
    assert "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5e", ""])
def test_an_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        peaks_for(kind)


def dev(platform, kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_a_cpu_or_too_few_chips_is_refused():
    with pytest.raises(NoChip):
        require_tpu([dev("cpu", "cpu")], 1)
    with pytest.raises(NoChip):
        require_tpu([dev("tpu")], 4)
    assert require_tpu([dev("tpu")] * 4, 4).platform == "tpu"


def test_run_exits_nonzero_with_no_result_line_on_the_cpu(capsys):
    from bench import run

    rc = run.main(["--workload", "yi34b-decode-fewlong", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU found" in out.err
