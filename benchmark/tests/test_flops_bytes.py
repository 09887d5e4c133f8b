"""The minimal pass at a hand-computed shape."""

import json
import os

import pytest

from benchmark import flops_bytes, harness


def test_pass_at_a_hand_computed_shape():
    # 1,000 rows x 39 ones = 39,000 nonzeros: X v multiplies and adds once
    # per nonzero (78,000), X^T d again (78,000)
    assert flops_bytes.pass_flops(1000, 39) == 156_000
    # per product an int32 index and a float32 element per nonzero:
    # 2 products x 39,000 x (4 + 4) B
    assert flops_bytes.pass_bytes(1000, 39) == 624_000


def test_bytes_bind_on_the_v5e():
    peaks = harness.load_peaks("TPU v5 lite")
    seconds, bound = flops_bytes.pass_roofline_seconds(1 << 19, 39, peaks)
    assert bound == "bytes"
    assert seconds == pytest.approx(16 * 39 * (1 << 19) / 8.19e11)


def test_peaks_cite_their_source_and_refuse_other_kinds():
    with open(os.path.join(harness.BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["_source"] and "Google Cloud" in peaks["_source"]
    assert peaks["TPU v5 lite"]["flops_per_s"] == 1.97e14
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(SystemExit):
        harness.load_peaks("TPU v9 imaginary")
    with pytest.raises(SystemExit):
        harness.load_peaks("_source")
