"""Cells at a size a CPU test can hold, driven through the harness's
runners with the look for a chip skipped."""

from __future__ import annotations

import copy
import json
import os
import sys

from bench import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))


def _read(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


TINY_CNN = {"input": [16, 16, 3], "blocks": [[8, 1], [16, 1]],
            "dense": [32, 10], "compile_options": {
                "target": "pallas", "autotune": "off",
                "embed_weights": False}}
TINY_LM = {"num_hidden_layers": 2, "hidden_size": 64,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
           "rope_theta": 10000.0,
           "serve": {"slots": 2, "max_len": 64, "prefill_chunk": 16,
                     "admission": "fcfs", "prefix_cache": 0}}
TINY_CHAT = {"rate_per_s": 50.0,
             "prompt_tokens": {"dist": "lognormal", "median": 12,
                               "sigma": 0.5, "min": 4, "max": 40},
             "output_tokens": {"dist": "lognormal", "median": 6,
                               "sigma": 0.5, "min": 2, "max": 16},
             "warmup": {"burst_output_tokens": 2, "seconds": 0.2},
             "drain_s": 30}


#: (configuration file, traffic mix) of the cells the tests drive
CELLS = {"vgg19.b1": ("bench/configs/vgg19.json", "closed_b1"),
         "vgg19.b32": ("bench/configs/vgg19.json", "closed_b32"),
         "qwen14b.chat": ("bench/configs/qwen2.5-14b.json", "chat")}


def cell(workload: str, config_over: dict, traffic_over: dict = None):
    """The cell ``workload`` (its committed configuration and traffic
    files) shrunk by ``config_over``/``traffic_over``."""
    cfg_file, mix = CELLS[workload]
    config = _read(cfg_file)
    config.update(copy.deepcopy(config_over))
    traffic = _read(f"bench/traffic/{mix}.json")
    traffic.update(copy.deepcopy(traffic_over or {}))
    return spec.Cell(name=workload, chips=1, config=config,
                     config_path=os.path.join(ROOT, cfg_file),
                     traffic=traffic, end_to_end=[], per_layer=[],
                     bench_dir=spec.BENCH_DIR)


class Watch:
    """Stand-in for run.py's compile counter."""

    def snapshot(self):
        return {"backend_compiles": 0}

    @staticmethod
    def since(a, b):
        return {k: b[k] - a[k] for k in b}


def drive(c, seed=3, seconds=0.5, broken=None, trace_dir=None):
    """One run of cell ``c`` through its runner on the CPU."""
    import importlib
    import time
    runner = importlib.import_module(f"bench.runners.{c.config['runner']}")
    return runner.run(c, c.config_module(), seed=seed, seconds=seconds,
                      trace_dir=trace_dir, t_start=time.perf_counter(),
                      watch=Watch(), say=lambda *a: None, broken=broken)
