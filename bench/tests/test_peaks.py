import json

import pytest

from bench import peaks


def test_v5e_peaks_are_the_published_ones():
    p = peaks.lookup("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup(kind)


def test_table_names_its_source():
    assert "TPU v5e" in json.loads(peaks.TABLE.read_text())["source"]
