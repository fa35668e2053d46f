"""The peak table: published numbers keyed by ``device_kind``, with a
source, and an error for a device it does not list."""
import json

import pytest

import peaks


def test_v5e_peaks_are_the_published_ones():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_table_names_its_source():
    assert "cloud.google.com" in json.loads(peaks.TABLE.read_text())["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "NVIDIA H100", ""])
def test_unknown_device_is_refused(kind):
    with pytest.raises(KeyError):
        peaks.peak(kind)
