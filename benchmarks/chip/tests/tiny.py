"""A tiny configuration and mix for CPU tests of the harness."""
import copy

import run
import traffic

CONFIG = {
    "name": "tiny",
    "source": "test",
    "arch": "qwen2",
    "hf_config": {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
                  "num_hidden_layers": 2, "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
                  "rope_theta": 10000.0, "tie_word_embeddings": False, "vocab_size": 512},
    "serving": {"dtype": "bfloat16", "slots": 2, "max_len": 256, "page_size": 16,
                "decode_block": 4},
    "stand_in": {"family": "qwen3", "name": "qwen3-4b"},
}

MIX = {
    "task": {"name": "mmlu", "task": "qa", "n_classes": 4, "sys_tokens": 40,
             "query_tokens": [20, 0.3], "out_tokens": [6, 0.15], "difficulty": [3.5, 2.8],
             "n_topics": 57, "sensitivity": 5.5},
    "query_tokens_max": 40, "answer_tokens_per_query": 4,
    "load": {"loop": "open", "rate_qps": 12.0, "budget_share": 0.5, "window_s": 0.1},
    "limits": {"plan_mismatches": 0, "prompt_mismatches": 0, "answer_mismatches": 0,
               "unanswered": 0, "logit_gap_mean": 0.02},
}

# The int8 control parts from the served bf16 path only at some width: at
# d 256 over ~1000 served tokens the program's mean gap read 0.0008-0.0015
# and the control's 0.0117-0.0193 (six seeds each, on the CPU).
WIDE_CONFIG = copy.deepcopy(CONFIG)
WIDE_CONFIG["hf_config"].update(hidden_size=256, num_hidden_layers=4, intermediate_size=512,
                                vocab_size=2048)
WIDE_CONFIG["serving"]["max_len"] = 512
WIDE_MIX = copy.deepcopy(MIX)
WIDE_MIX["answer_tokens_per_query"] = 16
WIDE_MIX["limits"]["logit_gap_mean"] = 0.005

CELL = {"name": "tiny.open", "config": "tiny", "traffic": "tiny", "chips": 1,
        "bench": {"per_layer": []}}


def closed_mix():
    mix = copy.deepcopy(MIX)
    mix["load"] = {"loop": "closed", "clients": 4, "budget_share": None, "window_s": 0.1}
    return mix


def small_modeling_stage(monkeypatch):
    """A modeling stage fitted on few queries, as fits a tiny mix: its plans
    batch the tiny mix's queries, so a fault in a batch can show."""
    monkeypatch.setattr(traffic, "SPLITS", {"train": 64, "val": 16})
    monkeypatch.setattr(traffic, "EMBED_DIM", 16)
    monkeypatch.setattr(run, "CORESET", 16)
