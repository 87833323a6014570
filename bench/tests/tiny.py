"""Cells for the CPU tests, written into a directory of their own: the
real cells' job at a tiny size (spec tests) and at a small one (the
comparison's tests).

The small cell carries limits of its own, set by the same rule as the real
cells' from CPU readings at its size (12 seeds each of sound runs, the int8
control and the faults; ``python3 bench/readings.py --specs``):

  grad_gap    sound 0.0023-0.0044, control from 0.0066, faults from 0.119
  grad_diff   sound 0.0082-0.0117, control from 0.0294, faults from 0.48
  change_gap  sound 0.0025-0.0052, faults from 0.048 (unchanged: 1)
  loss_gap    sound 0.0005-0.0023, control and faults from 0.0007: no
              upper reading, not compared
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench import harness

PUBLISHED = {"hidden_size": 64, "intermediate_size": 128,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512,
             "rms_norm_eps": 1e-06, "rope_theta": 1000000,
             "tie_word_embeddings": True}
MODEL = {"name": "tiny", "n_layers": 2, "d_model": 64, "n_heads": 4,
         "n_kv_heads": 2, "d_head": 16, "d_ff": 128, "vocab_size": 512,
         "tie_embeddings": True, "rope_theta": 1e6, "norm_eps": 1e-06}


SMALL = {"hidden_size": 256, "intermediate_size": 768,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
         "num_hidden_layers": 4, "vocab_size": 4096}
SMALL_LIMITS = {"loss_gap": None, "grad_gap": 0.02, "grad_diff": 0.02,
                "change_gap": 0.02}
PAIRS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
         "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
         "head_dim": "d_head", "intermediate_size": "d_ff",
         "vocab_size": "vocab_size"}


def write_cell(root: Path, *, like: str = "qwen3-1.7b.lora-bf16",
               small: bool = False, name: str = "tiny",
               reference: str = "qwen3_dense") -> Path:
    """``configs/``, ``traffic/`` and ``workloads/`` for a cell ``name``
    that keeps ``like``'s job, control and faults; the small cell has its
    own limits and sequence.  The configuration names ``reference``; the
    benchmark's module of that name, where there is one, is copied into
    ``references/``."""
    real = harness.load_cell(like)
    published, model, seq = dict(PUBLISHED), dict(MODEL), 64
    if small:
        published.update(SMALL)
        model.update({PAIRS[k]: v for k, v in SMALL.items()})
        seq = 128
    for kind in ("configs", "traffic", "workloads", "references"):
        (root / kind).mkdir(parents=True, exist_ok=True)
    module = harness.BENCH / "references" / f"{reference}.py"
    if module.is_file():
        shutil.copy(module, root / "references")
    config = dict(published, source="test", arch="qwen3-1.7b",
                  reference=reference, reduced=[], model=model)
    (root / "configs" / f"{name}.json").write_text(json.dumps(config))
    (root / "traffic" / f"{name}-mix.json").write_text(
        json.dumps(dict(real["mix"], seq=seq)))
    cell = {k: real[k] for k in ("chips", "mesh", "control", "faults",
                                 "limits", "why")}
    cell.update(config=name, traffic=f"{name}-mix")
    if small:
        cell["limits"] = SMALL_LIMITS
    (root / "workloads" / f"{name}.json").write_text(json.dumps(cell))
    return root
