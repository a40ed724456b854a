from types import SimpleNamespace

import jax
import numpy as np
import pytest

import run
import tiny
import work

A = dict(d=64, heads=4, kv_heads=2, head_dim=16, layers=3, vocab=100, matmul_params=30000)


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
    """The qwen2 module's ``matmul_params`` is the size of its layers'
    matrices, and a token's model flops are 2 a matrix parameter, plus
    attention and the head."""
    module = run.load_arch(tiny.CONFIG)
    a = module.arch(tiny.CONFIG)
    blocks = module.abstract_params(a)["blocks"]
    p = sum(int(np.prod(leaf.shape[1:])) for path, leaf
            in jax.tree_util.tree_leaves_with_path(blocks) if path[-1].key == "w")
    assert a["matmul_params"] == p
    assert work.decode_flops(a, 10) == (2 * p * a["layers"] + work.paged_attention(a, 10)[0]
                                        + 2 * a["d"] * a["vocab"])
    assert work.prefill_flops(a, 10) == (2 * p * a["layers"] * 10
                                         + work.flash_attention(a, 10)[0]
                                         + 2 * a["d"] * a["vocab"])


def test_a_modules_own_attention_counts_are_used():
    own = SimpleNamespace(paged_attention=lambda a, context: (7.0 * context, 1.0),
                          flash_attention=lambda a, n: (5.0 * n, 2.0))
    assert work.paged_attention(A, 10, own) == (70.0, 1.0)
    assert work.flash_attention(A, 10, own) == (50.0, 2.0)
    head = 2 * A["d"] * A["vocab"]
    assert work.decode_flops(A, 10, own) == 2 * A["matmul_params"] * A["layers"] + 70.0 + head
    assert work.prefill_flops(A, 10, own) == (2 * A["matmul_params"] * A["layers"] * 10 + 50.0
                                              + head)
    # a module without its own counts takes the generic ones
    assert work.paged_attention(A, 10, SimpleNamespace()) == work.paged_attention(A, 10)


def test_roofline_share_takes_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share(100.0, 5.0, 2.0, peak) == 50.0      # compute-bound
    assert work.roofline_share(1.0, 40.0, 8.0, peak) == 50.0       # memory-bound


def test_unknown_device_kind_is_an_error():
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("cpu")
