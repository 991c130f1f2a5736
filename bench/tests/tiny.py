"""A throwaway benchmark tree at smoke size, for CPU tests of the harness:
one configuration, two mixes, two cells, written under a temporary root,
beside a copy of the benchmark's architecture modules."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_CONFIG = {
    "source": "test", "model_type": "tiny", "arch": "dense",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 256,
    "max_position_embeddings": 128, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "norm": "layernorm",
    "engine": {"slots": 4, "max_len": 128, "page_size": 16,
               "num_pages": 32, "decode_chunk": 4, "prefill_quantum": 32,
               "prefix_caching": True, "attn_impl": "jnp"},
}
TINY_MIXES = {
    "chat": {"loop": "open", "rate_per_s": 12.0,
             "shared_prefix": {"count": 2, "tokens": 32, "zipf_s": 1.0},
             "prompt": {"min": 8, "max": 40, "median": 16, "sigma": 0.5},
             "output": {"min": 4, "max": 12, "median": 6, "sigma": 0.5}},
    "decode": {"loop": "closed", "clients_per_slot": 2,
               "prompt": {"min": 8, "max": 32, "median": 16, "sigma": 0.4},
               "output": {"min": 8, "max": 24, "median": 12, "sigma": 0.4}},
}


def make_root(root: str, config: dict = None, gap_limit: float = 1e9,
              mean_limit: float = 1e9, extra_metrics: dict = None,
              extra_arches: dict = None) -> str:
    """Write BENCHMARK.json, the configuration, the mixes and the cells
    under ``root``, with the benchmark's architecture modules and any in
    ``extra_arches`` (name -> source); returns ``root``."""
    config = config or TINY_CONFIG
    for d in ("configs", "traffic", "cells", "metrics"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "arch"),
                    os.path.join(root, "bench", "arch"), dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, body in (extra_arches or {}).items():
        with open(os.path.join(root, "bench", "arch", name + ".py"),
                  "w") as f:
            f.write(body)
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(config, f)
    cells = []
    for mix, params in TINY_MIXES.items():
        with open(os.path.join(root, "bench", "traffic", mix + ".json"),
                  "w") as f:
            json.dump(params, f)
        name = f"tiny.{mix}"
        with open(os.path.join(root, "bench", "cells", name + ".json"),
                  "w") as f:
            json.dump({"checks": {"max_logit_gap": gap_limit,
                                  "mean_logit_gap": mean_limit}}, f)
        cells.append({"name": name, "config": "tiny", "traffic": mix,
                      "chips": 1, "why": "test"})
    per_layer = []
    for name, body in (extra_metrics or {}).items():
        with open(os.path.join(root, "bench", "metrics", name + ".py"),
                  "w") as f:
            f.write(body)
        per_layer.append({"name": name, "unit": "frac", "better": "higher",
                          "source": "program_counter", "layer": "test",
                          "moves": "tok_per_s"})
    spec = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
            "run_seconds": 1,
            "configs": [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}],
            "workloads": cells,
            "end_to_end": [
                {"name": n, "unit": u, "better": b, "bound": 0.25,
                 "source": "host_clock"}
                for n, u, b in (("tok_per_s", "tokens/s", "higher"),
                                ("ttft_p95_ms", "ms", "lower"),
                                ("tpot_p95_ms", "ms", "lower"),
                                ("setup_s", "s", "lower"))],
            "per_layer": per_layer}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root
