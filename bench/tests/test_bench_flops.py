"""Work and peaks, checked against counts worked by hand."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import small  # noqa: E402,F401

from bench import cell, flops, peaks  # noqa: E402


def dims(name):
    return cell.model_dims(cell.config(name))


def test_parameter_counts():
    # minitron-4b, non-gated: per layer q, o 3072x3072; k, v 3072x1024;
    # up, down 3072x9216 -> 81,788,928; 32 layers plus the 256,000-row
    # embedding and unembedding of width 3072.
    assert flops.layer_params(dims("minitron-4b")) == 81_788_928
    assert flops.params(dims("minitron-4b")) == 4_190_109_696
    # granite-34b cut to 11 layers: q, o 6144x6144; k, v 6144x128 (one KV
    # head); up, down 6144x24576 -> 379,060,224; plus 2 x 49,152 x 6,144.
    assert flops.layer_params(dims("granite-34b")) == 379_060_224
    assert flops.params(dims("granite-34b")) == 4_773_642_240


def test_token_flops_by_hand():
    m = dims("minitron-4b")
    # position 0 attends one key: 2 FLOPs per MAC over QK^T and PV.
    attn = 4 * 1 * 24 * 128
    assert flops.token_flops(m, 0, logits=False) == 32 * (
        2 * 81_788_928 + attn)
    assert flops.token_flops(m, 0, logits=True) == 32 * (
        2 * 81_788_928 + attn) + 2 * 3072 * 256_000
    # at position 999 the attention term is a thousand times larger.
    assert (flops.token_flops(m, 999, False) - flops.token_flops(m, 0, False)
            == 32 * 4 * 999 * 24 * 128)


@pytest.mark.parametrize("name", ["minitron-4b", "granite-34b"])
def test_span_is_the_sum_of_its_tokens(name):
    m = dims(name)
    for start, end, last in ((0, 16, False), (1024, 1037, True), (5, 6, True)):
        want = sum(flops.token_flops(m, p, logits=last and p == end - 1)
                   for p in range(start, end))
        assert flops.span_flops(m, start, end, last) == pytest.approx(
            want, rel=1e-12)
    assert flops.span_flops(m, 7, 7, True) == 0.0


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
