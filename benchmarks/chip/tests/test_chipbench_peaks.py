"""The peak table is keyed by device kind and refuses a kind it lacks."""
import pytest

from chipbench_helpers import BENCH  # noqa: F401  (puts the repo on sys.path)
from benchmarks.chip import device


def test_v5e_peaks():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12
    assert p["hbm_byte_per_s"] == 819e9
    assert "source" in p


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_kind_is_refused(kind):
    with pytest.raises(KeyError):
        device.peaks(kind)


def test_cpu_host_is_refused():
    with pytest.raises(device.NoChip):
        device.require_chips(1)
