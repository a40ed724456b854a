"""The plain reference agrees with the program's own forward pass at float32,
and its int8 control departs from it far more than the served bf16 path."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import run
import tiny
import weights
from repro.models.transformer import Model
from repro.serving.engine import Request, ServingEngine


def config(tied: bool, dtype: str) -> dict:
    cfg = copy.deepcopy(tiny.CONFIG)
    cfg["hf_config"]["tie_word_embeddings"] = tied
    cfg["serving"]["dtype"] = dtype
    return cfg


@pytest.mark.parametrize("tied", [False, True])
def test_reference_matches_program_forward_at_f32(tied):
    cfg = config(tied, "float32")
    module = run.load_arch(cfg)
    a = module.arch(cfg)
    model = Model(module.model_config(cfg))
    abstract = module.abstract_params(a, jnp.float32)
    run.check_layout(model, abstract)
    params = weights.build(abstract, a["d"], tied, 3)
    toks = np.random.default_rng(0).integers(0, a["vocab"], (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, jnp.asarray(toks))
    got = reference.logits(module, params, a, [list(t) for t in toks],
                           [np.arange(40)] * 2, length=64, rows=2)
    np.testing.assert_allclose(np.stack(got), np.asarray(want)[..., : a["vocab"]],
                               rtol=2e-4, atol=2e-4)


def served(cfg: dict, seed: int, n_req: int = 3):
    """Greedy requests served by the program's paged engine in bf16."""
    module = run.load_arch(cfg)
    a = module.arch(cfg)
    model = Model(module.model_config(cfg))
    params = weights.build(module.abstract_params(a), a["d"], a["tied"], seed)
    s = cfg["serving"]
    eng = ServingEngine(model, params, max_slots=s["slots"], max_len=s["max_len"],
                        decode_block=s["decode_block"], paged=True, page_size=s["page_size"])
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, tokens=[257] + list(rng.integers(32, 127, 60)), max_new=24)
            for i in range(n_req)]
    for r in reqs:
        eng.serve([r])
    return module, a, [(r.tokens, r.out_tokens) for r in reqs]


def test_int8_control_departs_more_than_served_bf16():
    """At a width a test can hold (d 256, 4 layers), on four seeds: the
    widest gap of the int8 control is wider than that of every served run."""
    cfg = config(False, "bfloat16")
    cfg["hf_config"].update(hidden_size=256, num_hidden_layers=4, intermediate_size=512,
                            vocab_size=2048)
    served_gap, control_gap = [], []
    for seed in (1, 2, 3, 4):
        module, a, reqs = served(cfg, seed, n_req=4)
        params = weights.build(module.abstract_params(a), a["d"], a["tied"], seed)
        served_gap.append(max(g.max() for g in
                              reference.served_gaps(module, params, a, reqs, 128)))
        control_gap.append(max(g.max() for g in
                               reference.served_gaps(module, params, a, reqs, 128, int8=True)))
    assert min(control_gap) > max(served_gap), (served_gap, control_gap)
