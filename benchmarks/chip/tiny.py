"""A throwaway cell at a tiny size, for the self-tests on the CPU.

:func:`make_root` copies the benchmark into a fresh directory and adds,
as new files only, a tiny configuration, a tiny mix, a throwaway metric
and a ``BENCHMARK.json`` that names them. The harness must find all of
them by name.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent

CONFIG = {
    "name": "tiny-dense", "registry": "deepseek-7b",
    "overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 96,
                  "vocab": 256},
    "source": "https://huggingface.co/deepseek-ai/deepseek-llm-7b-base",
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "reduced": {}, "chips": 1,
    "engine": {"n_slots": 4, "max_len": 256, "page_size": 8,
               "prefill_chunk": 64, "prefix_cache": True, "eos_id": -1},
}

BATCH = {
    "kind": "closed_backlog", "shape_seed": 5, "n_requests": 80,
    "order_block": 4,
    "prompt_len": {"median": 40, "sigma": 0.8, "min": 8, "max": 160},
    "output_len": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
    "check": {"min_tokens": 200, "max_requests": 24},
}

DOCQA = {
    "kind": "closed_backlog", "shape_seed": 6, "n_sessions": 12,
    "order_block": 2,
    "doc_len": {"median": 100, "sigma": 0.5, "min": 60, "max": 160},
    "questions": {"min": 2, "max": 3},
    "question_len": {"min": 4, "max": 12}, "answer_len": {"min": 4,
                                                          "max": 12},
    "check": {"min_tokens": 200, "max_requests": 24},
}

# the tiny cells' limit on the served-token logit gap: on the CPU the
# program read 0-0.0078 and the fp8 control 0.126-0.249 (3 seeds each)
LIMIT = 0.05

METRIC = '''"""Throwaway metric: requests that produced a token."""


def read(rec):
    return float(sum(1 for r in rec["rows"] if r["tokens"]))
'''


def make_root(tmp: Path, limit: float = LIMIT) -> Path:
    """A checkout-shaped directory holding the benchmark plus the
    throwaway cells ``tiny-batch`` and ``tiny-docqa``."""
    bench = tmp / "benchmarks" / "chip"
    shutil.copytree(HERE, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    (bench / "configs" / "tiny-dense.json").write_text(json.dumps(CONFIG))
    (bench / "traffic" / "tiny-batch.json").write_text(json.dumps(BATCH))
    (bench / "traffic" / "tiny-docqa.json").write_text(json.dumps(DOCQA))
    (bench / "metrics" / "tiny_requests.py").write_text(METRIC)
    for cell in ("tiny-batch", "tiny-docqa"):
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(
            {"served_logit_gap": {"limit": limit}}))
    spec = {
        "command": ["python3", "benchmarks/chip/run.py"],
        "paths": ["benchmarks/chip"], "run_seconds": 2,
        "configs": [{"name": "tiny-dense", "source": CONFIG["source"],
                     "file": "benchmarks/chip/configs/tiny-dense.json",
                     "reduced": [], "why": "self-test"}],
        "workloads": [
            {"name": "tiny-batch", "config": "tiny-dense",
             "traffic": "tiny-batch", "chips": 1, "why": "self-test"},
            {"name": "tiny-docqa", "config": "tiny-dense",
             "traffic": "tiny-docqa", "chips": 1, "why": "self-test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "tokens_per_s.tiny", "unit": "tokens/s",
             "better": "higher", "bound": 0.25, "source": "host_clock",
             "workloads": ["tiny-docqa"]},
            {"name": "tiny_requests", "unit": "count", "better": "higher",
             "bound": 0.25, "source": "host_clock",
             "workloads": ["tiny-batch"]}],
        "per_layer": [
            {"name": "batch_occupancy", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "scheduler",
             "moves": "tokens_per_s.tiny", "workloads": ["tiny-docqa"]}],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
