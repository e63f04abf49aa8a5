"""A benchmark root at smoke size for the CPU tests: a copy of ``bench/``
beside a ``BENCHMARK.json`` that adds one cell, from a configuration file
and a mix file of its own, to the committed benchmark."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
# the seed of the smoke runs, and of the weights checksum in test_chips.py
SEED = 2**40 + 5

SMOKE_CONFIG = {
    "name": "smoke-qwen2", "model_type": "qwen2", "hidden_act": "silu",
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "vocab_size": 512,
    "serving": {"chips": 1, "batch_slots": 4, "max_len": 256,
                "page_size": 16, "cache_pages": 48, "prefill_chunk": 32,
                "check_min_tokens": 200, "check_max_requests": 8},
    # as at published widths, the mean gap is compared: sound runs read
    # 0 to 0.0003 (seeds 2**40+5, 2**40+9, 7), half a batch left out 0.21
    # to 0.57
    "correct": {"mean_gap": 0.0008},
}

SMOKE_MIX = {
    "system_prompts": 2, "system_prompt_tokens": 48,
    "user_tokens": {"median": 16, "sigma": 0.8, "min": 4, "max": 48},
    "answer_tokens": {"median": 16, "sigma": 0.6, "min": 4, "max": 40},
    "lengths_per_run": 256, "lengths_seed": 5, "ramp_steps": 4,
}


def make_root(tmp: Path, config=SMOKE_CONFIG, mix=SMOKE_MIX,
              name="smoke.chat", chips=None) -> dict:
    """Copy the benchmark into ``tmp`` and add the smoke cell, on the chips
    its configuration names unless ``chips`` says otherwise; returns the
    new ``BENCHMARK.json``."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp / "bench" / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (tmp / "bench" / "traffic" / "smoke.json").write_text(json.dumps(mix))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": config["name"], "source": "smoke size, CPU tests only",
        "file": f"bench/configs/{config['name']}.json", "reduced": [],
        "why": "CPU rehearsal"})
    bench["workloads"].append({
        "name": name, "config": config["name"], "traffic": "smoke",
        "chips": config["serving"]["chips"] if chips is None else chips,
        "why": "CPU rehearsal"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench

# wide enough that int8 weights, activations and KV pages read apart from
# bf16 (at SMOKE_CONFIG's width they do not). The check draws 300-450
# served tokens here (11-16 requests of a 6 s window on the CPU); seeds
# 1-6 read, program then int8 control: max_gap 0.0642, 0.0337, 0.0600,
# 0.0333, 0.0376, 0.0626 (highest 0.0642) and 0.2164, 0.1948, 0.1833,
# 0.2372, 0.3387, 0.3074 (lowest 0.1833); mean_gap 0.00084, 0.00075,
# 0.00113, 0.00053, 0.00069, 0.00067 (highest 0.00113) and 0.0098,
# 0.0107, 0.0094, 0.0108, 0.0127, 0.0123 (lowest 0.0094). Each limit lies
# between, nearer the geometric mean than either end
CONTROL_CONFIG = dict(
    SMOKE_CONFIG, name="mid-qwen2", hidden_size=512, intermediate_size=1536,
    num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
    head_dim=64, vocab_size=8192,
    serving=dict(SMOKE_CONFIG["serving"], max_len=512, cache_pages=128,
                 prefill_chunk=64, check_min_tokens=400,
                 check_max_requests=16),
    correct={"max_gap": 0.11, "mean_gap": 0.0035})
CONTROL_MIX = dict(
    SMOKE_MIX, system_prompt_tokens=128,
    user_tokens={"median": 32, "sigma": 0.8, "min": 8, "max": 96},
    answer_tokens={"median": 32, "sigma": 0.6, "min": 8, "max": 96})
