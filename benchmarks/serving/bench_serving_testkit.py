"""Helpers for the benchmark's CPU tests: a copy of the benchmark's files
with one more configuration and cell at a size a test run can hold."""
from __future__ import annotations

import json
import pathlib
import shutil

HERE = pathlib.Path(__file__).resolve().parent

#: widths of the CPU cell: every published key of a dense decoder, tiny
TINY_CONFIG = {
    "name": "tiny_dense",
    "source": "test configuration: phi3_mini's layout at widths a CPU "
              "test can run",
    "family": "dense",
    "arch": "phi3-mini-3.8b",
    "overrides": {"n_layers": 2, "d_model": 128, "n_heads": 4,
                  "n_kv_heads": 4, "d_ff": 256, "vocab_size": 512},
    "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "reduced": ["num_hidden_layers", "hidden_size", "intermediate_size",
                "vocab_size"],
    "assumed": {}, "deployment": "a CPU test",
    "engine": {"max_batch": 4, "max_seq": 128, "block_tokens": 8,
               "pool_tokens": 512, "chunk": 32},
}

TINY_CELL = {
    "name": "tiny_dense.mixed",
    "config": "tiny_dense",
    "arrivals": {"kind": "closed", "clients": 4},
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                   "min": 4, "max": 70},
    "output_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                   "min": 4, "max": 48},
    "requests": 1024, "strata": 16,
    "check": {"sample_tokens": 120, "logit_gap_limit": 0.15},
    "trace": {"start_s": 0.5, "seconds": 1.0},
    "why": "CPU test cell", "who": "tests",
}


def tiny_bench(tmp: pathlib.Path, cell: dict | None = None) -> pathlib.Path:
    """A copy of the benchmark's data and readers under ``tmp`` with the
    tiny configuration and cell added; returns the copy's directory."""
    base = tmp / "serving"
    shutil.copytree(HERE, base, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata*"))
    (base / "configs" / "tiny_dense.json").write_text(json.dumps(TINY_CONFIG))
    cell = cell or TINY_CELL
    (base / "traffic" / f"{cell['name']}.json").write_text(json.dumps(cell))
    return base
