"""A checkout-shaped directory with tiny cells, for the harness's CPU tests.

``make_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` and adds four
cells at the program's reduced sizes (float32, a few layers of width 64):
``tiny-lm.sketchy`` and ``tiny-lm.adam`` (dense, the reference of
paper-lm-100m), ``tiny-ssm.sketchy`` and ``tiny-ssm.adam`` (Mamba2, the
reference ``mamba2_ref.py`` beside this file).  They are added as new files
and entries only, and found by name exactly as the real cells are.
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

# the program's reduced mamba2-370m: 3 layers, d_model 64, 8 heads of 16,
# state 16; chunks of 16 over 64 tokens carry the state across 4 chunks
TINY_SSM = {
    "name": "tiny-ssm", "registry": "mamba2-370m", "program_reduced": True,
    "family": "ssm", "batch": 4, "seq": 64, "param_dtype": "float32",
    "reference_rows": 2,
    "model": {"n_layer": 3, "d_model": 64, "d_state": 16, "d_conv": 4,
              "expand": 2, "headdim": 16, "ngroups": 1, "chunk_size": 16,
              "vocab_size": 256, "norm_epsilon": 1e-06,
              "tie_embeddings": True, "torch_dtype": "float32"},
    "program_fields": {"num_layers": "n_layer", "d_model": "d_model",
                       "ssm_state": "d_state", "ssm_conv_width": "d_conv",
                       "ssm_expand": "expand", "ssm_head_dim": "headdim",
                       "ssm_chunk": "chunk_size", "vocab_size": "vocab_size",
                       "norm_eps": "norm_epsilon",
                       "tie_embeddings": "tie_embeddings",
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
    refs = [(TINY_LM, os.path.join(b, "configs", "paper-lm-100m.py")),
            (TINY_SSM, os.path.join(os.path.dirname(__file__),
                                    "mamba2_ref.py"))]
    for config, ref in refs:
        _dump(os.path.join(b, "configs", config["name"] + ".json"), config)
        shutil.copy(ref, os.path.join(b, "configs", config["name"] + ".py"))
    for mix in (TINY_SKETCHY, TINY_ADAM):
        _dump(os.path.join(b, "mixes", mix["name"] + ".json"), mix)
    # each tiny cell reports what the real cell of its mix reports
    cells = [(f"{cfg}.{kind}", cfg, f"tiny-{kind}", f"paper-lm-100m.{kind}")
             for cfg in ("tiny-lm", "tiny-ssm") for kind in ("sketchy", "adam")]
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
