"""A checkout-shaped directory with tiny cells, for the harness's CPU tests.

``make_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` and adds two
cells at the program's reduced sizes (float32, a few layers of width 64):
``tiny-lm.sketchy`` and ``tiny-lm.adam``.  They are found by name exactly
as the real cells are.
"""
from __future__ import annotations

import json
import os
import shutil

from bench import spec as spec_lib

TINY_LM = {
    "name": "tiny-lm", "registry": "paper-lm-100m", "program_reduced": True,
    "family": "dense", "batch": 4, "seq": 64, "param_dtype": "float32",
    "reference_rows": 2,
    "model": {"num_hidden_layers": 3, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
              "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
              "tie_word_embeddings": False, "torch_dtype": "float32"},
    "program_fields": {"num_layers": "num_hidden_layers",
                       "d_model": "hidden_size",
                       "num_heads": "num_attention_heads",
                       "num_kv_heads": "num_key_value_heads",
                       "head_dim": "head_dim", "d_ff": "intermediate_size",
                       "vocab_size": "vocab_size", "norm_eps": "rms_norm_eps",
                       "dtype": "torch_dtype"},
}

TINY_SKETCHY = {
    "name": "tiny-sketchy", "optimizer": "sketchy", "period_steps": 2,
    "train_argv": ["--optimizer", "sketchy", "--block-size", "32", "--rank",
                   "4", "--update-every", "2", "--lr", "3e-3", "--steps",
                   "20", "--profile-annotations"],
    "hyper": {"lr": 0.003, "total_steps": 20, "warmup_frac": 0.05,
              "beta1": 0.9, "beta2": 0.999, "weight_decay": 0.0001,
              "clip": 1.0, "block_size": 32, "rank": 4, "update_every": 2,
              "matrix_eps": 1e-06, "graft_eps": 1e-08},
    "program_fields": {"learning_rate": "lr", "total_steps": "total_steps",
                       "block_size": "block_size", "rank": "rank",
                       "update_every": "update_every"},
}

TINY_ADAM = {
    "name": "tiny-adam", "optimizer": "adam", "period_steps": 1,
    "train_argv": ["--optimizer", "adam", "--lr", "3e-3", "--steps", "20"],
    "hyper": {"lr": 0.003, "total_steps": 20, "warmup_frac": 0.05,
              "beta1": 0.9, "beta2": 0.999, "eps": 1e-08,
              "weight_decay": 0.0001, "clip": 1.0},
    "program_fields": {"learning_rate": "lr", "total_steps": "total_steps"},
}

# float32 program against the float32 reference at these sizes: the gaps
# are rounding, far below these limits
TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-2}


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp: str) -> str:
    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(os.path.join(spec_lib.ROOT, "bench"),
                    os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = spec_lib.load_spec()
    b = os.path.join(root, "bench")
    _dump(os.path.join(b, "configs", TINY_LM["name"] + ".json"), TINY_LM)
    shutil.copy(os.path.join(b, "configs", "paper-lm-100m.py"),
                os.path.join(b, "configs", TINY_LM["name"] + ".py"))
    for mix in (TINY_SKETCHY, TINY_ADAM):
        _dump(os.path.join(b, "mixes", mix["name"] + ".json"), mix)
    # each tiny cell reports what the real cell of its mix reports
    cells = [("tiny-lm.sketchy", "tiny-lm", "tiny-sketchy",
              "paper-lm-100m.sketchy"),
             ("tiny-lm.adam", "tiny-lm", "tiny-adam", "paper-lm-100m.adam")]
    for name, cfg, mix, twin in cells:
        spec["workloads"].append({"name": name, "config": cfg,
                                  "traffic": mix, "chips": 1,
                                  "why": "CPU test cell"})
        _dump(os.path.join(b, "limits", name + ".json"), TINY_LIMITS)
        for m in spec["end_to_end"] + spec["per_layer"]:
            if twin in m.get("workloads", []):
                m["workloads"].append(name)
    _dump(os.path.join(root, "BENCHMARK.json"), spec)
    return root
