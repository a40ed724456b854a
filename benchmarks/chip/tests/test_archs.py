"""Architecture modules: the configuration names its module, and the harness
reaches the architecture only through it.

``qwen2`` gives what the harness gave before its move into ``archs/``: the
reference's logits and the work counts equal goldens saved from the earlier
harness.  A second architecture (GQA 4:1, a head size that is not d/h, QK
RMSNorm, no bias, untied head) joins as files under ``tests/data/`` alone and
matches the program at float32, in its forward and through paged prefill and
decode.
"""
import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import run
import tiny
import weights
import work
from repro.models.transformer import Model
from repro.serving.engine import Request, ServingEngine

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
QWEN05B = os.path.join(run.HERE, "configs", "qwen1.5-0.5b.json")


@pytest.fixture
def test_archs(monkeypatch):
    """The loader pointed at ``tests/data/``, and that directory's config."""
    monkeypatch.setattr(run, "ARCH_DIR", DATA)
    return run.load_json(os.path.join(DATA, "qknorm_gqa.json"))


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_qwen2_reference_logits_equal_the_goldens(tied, int8):
    """At ``tiny.CONFIG`` in float32, seed 3: the module's forward gives the
    logits the reference gave before the move, and its int8 control too."""
    golden = np.load(os.path.join(DATA, "qwen2_reference.npz"))
    cfg = copy.deepcopy(tiny.CONFIG)
    cfg["hf_config"]["tie_word_embeddings"] = tied
    module = run.load_arch(cfg)
    a = module.arch(cfg)
    params = weights.build(module.abstract_params(a, jnp.float32), a["d"], tied, 3)
    got = reference.logits(module, params, a, [list(t) for t in golden["tokens"]],
                           list(golden["positions"]), length=64, rows=2, int8=int8)
    np.testing.assert_allclose(np.stack(got), golden[f"logits_tied{int(tied)}_int8{int(int8)}"],
                               rtol=1e-6, atol=1e-6)


def test_qwen2_work_counts_equal_the_goldens():
    """At the Qwen1.5-0.5B shapes: the counts ``step_mfu`` and both rooflines
    read are those of the harness before the move, exactly."""
    with open(os.path.join(DATA, "qwen2_work.json")) as f:
        golden = json.load(f)
    cfg = run.load_json(QWEN05B)
    module = run.load_arch(cfg)
    a = module.arch(cfg)
    counts = {"prefill_flops": work.prefill_flops, "decode_flops": work.decode_flops,
              "paged_attention": work.paged_attention, "flash_attention": work.flash_attention}
    for key, want in golden.items():
        name, n = key.split("/")
        got = counts[name](a, int(n), module)
        assert (list(got) if isinstance(got, tuple) else got) == want, key


def test_config_without_arch_key_is_a_setup_error():
    cfg = {k: v for k, v in tiny.CONFIG.items() if k != "arch"}
    with pytest.raises(SystemExit, match=r'configs/tiny\.json: no "arch" key'):
        run.load_arch(cfg, "configs/tiny.json")


def test_unknown_arch_module_is_a_setup_error():
    cfg = dict(tiny.CONFIG, arch="no_such_arch")
    with pytest.raises(SystemExit, match=r"configs/tiny\.json: .*archs/no_such_arch\.py"):
        run.load_arch(cfg, "configs/tiny.json")


def test_every_benchmark_config_names_a_module():
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    for c in bench["configs"]:
        cfg = run.load_json(os.path.join(run.ROOT, c["file"]))
        module = run.load_arch(cfg, c["file"])
        assert module.arch(cfg)["matmul_params"] > 0


def test_new_arch_from_files_alone_matches_the_program_forward(test_archs):
    cfg = test_archs
    module = run.load_arch(cfg)
    a = module.arch(cfg)
    assert a["head_dim"] != a["d"] // a["heads"] and a["heads"] == 4 * a["kv_heads"]
    model = Model(module.model_config(cfg))
    abstract = module.abstract_params(a, jnp.float32)
    run.check_layout(model, abstract)
    params = weights.build(abstract, a["d"], a["tied"], 5)
    toks = np.random.default_rng(1).integers(0, a["vocab"], (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, jnp.asarray(toks))
    got = reference.logits(module, params, a, [list(t) for t in toks],
                           [np.arange(40)] * 2, length=64, rows=2)
    # the tolerance of test_reference.py: the same float32 arithmetic in
    # another order
    np.testing.assert_allclose(np.stack(got), np.asarray(want)[..., : a["vocab"]],
                               rtol=2e-4, atol=2e-4)


def test_new_arch_from_files_alone_serves_paged_as_the_reference(test_archs):
    """Paged prefill and decode through ``ServingEngine`` at float32: every
    served greedy token is the reference's best, to rounding, and
    ``work.decode_flops`` counts the module's shapes."""
    cfg = test_archs
    module = run.load_arch(cfg)
    a = module.arch(cfg)
    model = Model(module.model_config(cfg))
    params = weights.build(module.abstract_params(a, jnp.float32), a["d"], a["tied"], 7)
    s = cfg["serving"]
    eng = ServingEngine(model, params, max_slots=s["slots"], max_len=s["max_len"],
                        decode_block=s["decode_block"], paged=True, page_size=s["page_size"])
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, tokens=[257] + list(rng.integers(32, 127, 50 + 7 * i)), max_new=24)
            for i in range(3)]
    eng.serve(reqs)
    served = [(r.tokens, r.out_tokens) for r in reqs]
    assert sum(len(t) for _, t in served) >= 40
    gaps = np.concatenate(reference.served_gaps(module, params, a, served, 128))
    # a served token off the reference's best by more than float32 rounding
    # of the forward (2e-4 a logit, twice) is a fault of prefill or decode
    assert gaps.max() <= 1e-3, gaps.max()

    hd, d, ff = a["head_dim"], a["d"], a["ff"]
    per_layer = 8 * d * hd + 2 * 2 * d * hd + 8 * hd * d + 3 * d * ff
    attention = a["layers"] * 4 * a["heads"] * hd * 100
    assert work.decode_flops(a, 100, module) == (2 * per_layer * a["layers"] + attention
                                                 + 2 * d * a["vocab"])
