"""A tiny copy of the benchmark for CPU tests: the same files, with each
configuration cut to the program's ``reduced()`` size, each mix to a few
requests or steps, and limits of its own, registered under names of
their own. The training cells, held out of ``BENCHMARK.json`` until the
program can tie SmolLM's output matrix to its embedding, are added to
the copy with an untied tiny SmolLM, so their harness stays tested.

The tiny limits were set as the cells' were, from CPU readings at these
sizes (6 seeds each): training, sound program loss 1.8e-4, grad 5.7e-4,
update 1.6e-4 against the float8 control's least 6.5e-4, 1.5e-3, 1.35e-3
and half a batch's 2.7e-3, 1.6e-2, 0.23; serving, sound program widest
logit gap 0 to 0.031 against the control's 0.037 to 0.24 (a random token
lies about 3 below the best).
"""
import copy
import json
import os
import shutil


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIGS = {
    "smollm-135m": {"num_hidden_layers": 2, "hidden_size": 256,
                    "num_attention_heads": 4, "num_key_value_heads": 1,
                    "head_dim": 64, "intermediate_size": 512,
                    "vocab_size": 1024, "tie_word_embeddings": False},
    "mamba2-130m": {"n_layer": 2, "d_model": 256, "vocab_size": 1024,
                    "ssd_chunk": 32,
                    "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4,
                                "expand": 2, "headdim": 64, "ngroups": 1}},
}
TINY_MIXES = {
    "train-1chip": {"seq": 64, "batch_per_chip": 4, "distinct_batches": 4,
                    "trace_steps": 2},
    "train-dp4-tuned": {"seq": 64, "batch_per_chip": 2, "distinct_batches": 4,
                        "trace_steps": 2},
    "chat-bursty": {"arrivals": {"kind": "gamma", "cv": 2.0, "rate_rps": 8.0},
                    "prompt_len": {"kind": "lognormal", "median": 40,
                                   "sigma": 0.5, "min": 8, "max": 64,
                                   "buckets": [32, 64]},
                    "output_len": {"kind": "lognormal", "median": 6,
                                   "sigma": 0.5, "min": 2, "max": 10},
                    "engine": {"max_active": 4, "block_size": 16},
                    "drain_s": 30, "trace_s": 1,
                    "check": {"requests": 3, "min_tokens": 10}},
}


TINY_LIMITS = {
    "train-1chip.smollm-135m": {"loss_gap": 3.5e-4, "grad_gap": 1e-3,
                                "update_gap": 5e-4},
    "train-dp4-tuned.smollm-135m": {"loss_gap": 3.5e-4, "grad_gap": 1e-3,
                                    "update_gap": 5e-4},
    "serve-chat-bursty.mamba2-130m": {"logit_gap": 0.1},
}


HELD_WORKLOADS = [
    {"name": "train-1chip.smollm-135m", "config": "smollm-135m",
     "traffic": "train-1chip", "chips": 1, "why": "one worker"},
    {"name": "train-dp4-tuned.smollm-135m", "config": "smollm-135m",
     "traffic": "train-dp4-tuned", "chips": 4, "why": "tuned gradient sync"},
]
HELD_METRICS = [
    {"name": "train_tok_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.01, "source": "host_clock",
     "workloads": [w["name"] for w in HELD_WORKLOADS]},
]
TABLE = os.path.join("examples", "artifacts", "tuned_decision.json")


def make_tiny_root(dest, monkeypatch, chips=1):
    """Copy BENCHMARK.json and bench/ to ``dest`` with tiny sizes, register
    the tiny program configurations, and return ``dest``."""
    from repro import configs
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for name, sizes in TINY_CONFIGS.items():
        path = os.path.join(dest, "bench", "configs", name + ".json")
        with open(path) as f:
            cfg = json.load(f)
        cfg.update(sizes)
        cfg["repro_config"] = name + "-tiny"
        with open(path, "w") as f:
            json.dump(cfg, f)
        arch = configs.ARCHITECTURES[name].reduced()
        monkeypatch.setitem(configs.ARCHITECTURES, name + "-tiny", arch)
    for name, over in TINY_MIXES.items():
        path = os.path.join(dest, "bench", "traffic", name + ".json")
        with open(path) as f:
            mix = json.load(f)
        mix.update(copy.deepcopy(over))
        with open(path, "w") as f:
            json.dump(mix, f)
    for name, lims in TINY_LIMITS.items():
        path = os.path.join(dest, "bench", "limits", name + ".json")
        with open(path, "w") as f:
            json.dump({"limits": lims}, f)
    os.makedirs(os.path.join(dest, os.path.dirname(TABLE)), exist_ok=True)
    shutil.copy(os.path.join(ROOT, TABLE), os.path.join(dest, TABLE))
    spec["workloads"] += copy.deepcopy(HELD_WORKLOADS)
    spec["end_to_end"] += copy.deepcopy(HELD_METRICS)
    for w in spec["workloads"]:
        w["chips"] = min(w["chips"], chips)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dest
