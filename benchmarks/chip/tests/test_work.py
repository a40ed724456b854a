import numpy as np
import pytest

import work

A = dict(d=64, heads=4, kv_heads=2, head_dim=16, ff=96, layers=3, vocab=100)


def test_flash_counts_causal_real_tokens():
    n = 37
    mask = np.tril(np.ones((n, n)))
    flops, nbytes = work.flash_attention(A, n)
    per_layer = 2 * 2 * A["heads"] * A["head_dim"] * mask.sum()
    assert flops == A["layers"] * per_layer
    elems = n * A["heads"] * A["head_dim"] * 2 + n * A["kv_heads"] * A["head_dim"] * 2
    assert nbytes == A["layers"] * elems * 2


def test_paged_counts_live_context_only():
    flops, nbytes = work.paged_attention(A, 100)
    assert flops == A["layers"] * 4 * A["heads"] * A["head_dim"] * 100
    kv = 2 * 100 * A["kv_heads"] * A["head_dim"]
    qo = 2 * A["heads"] * A["head_dim"]
    assert nbytes == A["layers"] * (kv + qo) * 2
    assert work.paged_attention(A, 200)[1] > nbytes


def test_model_flops_match_a_count_of_matmuls():
    p = (A["d"] * A["heads"] * A["head_dim"] + 2 * A["d"] * A["kv_heads"] * A["head_dim"]
         + A["heads"] * A["head_dim"] * A["d"] + 3 * A["d"] * A["ff"])
    assert work.matmul_params_per_layer(A) == p
    assert work.decode_flops(A, 10) == (2 * p * A["layers"] + work.paged_attention(A, 10)[0]
                                        + 2 * A["d"] * A["vocab"])
    assert work.prefill_flops(A, 10) == (2 * p * A["layers"] * 10
                                         + work.flash_attention(A, 10)[0]
                                         + 2 * A["d"] * A["vocab"])


def test_roofline_share_takes_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share(100.0, 5.0, 2.0, peak) == 50.0      # compute-bound
    assert work.roofline_share(1.0, 40.0, 8.0, peak) == 50.0       # memory-bound


def test_unknown_device_kind_is_an_error():
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("cpu")
