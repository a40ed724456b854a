"""The whole run, off the chip at a tiny size, with the timed path broken
underneath: ``correct`` must come out false for each fault the cells can
have, and for the int8 control in the program's place, and true without
one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import tiny
from repro.serving.batcher import BatchPromptFormatter
from repro.serving.engine import ServingEngine
from repro.serving.pool import ServedPoolMember

SEED = 2**33 + 5


@pytest.fixture(autouse=True)
def cpu_devices(monkeypatch):
    """Everything of a run but its look for a chip."""
    def devices(chips):
        devs = jax.devices()
        return devs, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                      "count": len(devs)}, None

    monkeypatch.setattr(run, "require_chips", devices)
    tiny.small_modeling_stage(monkeypatch)


def run_tiny(mix=None, config=None, seconds=2.0):
    result, lines = run.run(tiny.CELL, config or tiny.CONFIG, mix or tiny.MIX, SEED,
                            seconds=seconds, trace=False)
    return result


def alter_decoded_tokens(monkeypatch):
    """Every fused decode block's last token is replaced where it is made."""
    init = ServingEngine.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        decode = self._decode_k_paged

        def broken(*args, **kwargs):
            cache, act, toks, valid = decode(*args, **kwargs)
            return cache, act, toks.at[-1].set((toks[-1] + 1) % 256), valid

        self._decode_k_paged = broken

    monkeypatch.setattr(ServingEngine, "__init__", patched)


def decode_leaves_the_cache_unchanged(monkeypatch):
    """Every fused decode block hands back the KV cache as it found it."""
    init = ServingEngine.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        decode = self._decode_k_paged

        def broken(params, cache, *args, **kwargs):
            before = jax.tree.map(jnp.copy, cache)
            _, act, toks, valid = decode(params, cache, *args, **kwargs)
            return before, act, toks, valid

        self._decode_k_paged = broken

    monkeypatch.setattr(ServingEngine, "__init__", patched)


def alter_answers(monkeypatch):
    parse = BatchPromptFormatter.parse
    monkeypatch.setattr(BatchPromptFormatter, "parse",
                        lambda self, out, b: [a + "x" for a in parse(self, out, b)])


def drop_half_the_batch(monkeypatch):
    invoke = ServedPoolMember.invoke_batch

    def broken(self, wl, batch_idx, *a, **kw):
        kept = np.asarray(batch_idx)[: (len(batch_idx) + 1) // 2]
        streams = kw.get("streams")
        if streams:
            kw["streams"] = {p: s for p, s in streams.items() if p < len(kept)}
        out = invoke(self, wl, kept, *a, **kw)
        missing = len(batch_idx) - len(kept)
        out.utilities = np.concatenate([out.utilities, np.zeros(missing)])
        out.answers = list(out.answers) + [""] * missing
        return out

    monkeypatch.setattr(ServedPoolMember, "invoke_batch", broken)


@pytest.mark.parametrize("mix", [tiny.MIX, tiny.closed_mix()], ids=["open", "closed"])
def test_sound_run_is_correct(mix):
    result = run_tiny(mix)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0


@pytest.mark.parametrize("fault, caught_by", [
    (alter_decoded_tokens, "logit_gap_mean"),
    (decode_leaves_the_cache_unchanged, "logit_gap_mean"),
    (alter_answers, "answer_mismatches"),
    (drop_half_the_batch, "prompt_mismatches"),
])
def test_fault_makes_run_incorrect(monkeypatch, fault, caught_by):
    fault(monkeypatch)
    result = run_tiny()
    assert not result["correct"]
    check = result["checks"][caught_by]
    assert check["value"] > check["limit"], result["checks"]


def test_int8_control_in_the_programs_place_is_not_correct(monkeypatch):
    """The reference in int8, the control of a bf16 model, in the program's
    place: the gaps of the tokens it puts first go through ``correct``."""
    gaps = run.logit_gaps
    monkeypatch.setattr(run, "logit_gaps",
                        lambda arch, served, seed, length, int8=False:
                        gaps(arch, served, seed, length, int8=True))
    result = run_tiny(tiny.WIDE_MIX, tiny.WIDE_CONFIG, seconds=8.0)
    assert not result["correct"]
    check = result["checks"]["logit_gap_mean"]
    assert check["value"] > check["limit"], result["checks"]


def test_sound_run_is_correct_at_the_controls_width():
    result = run_tiny(tiny.WIDE_MIX, tiny.WIDE_CONFIG, seconds=8.0)
    assert result["correct"], result["checks"]
