"""Smoke-size cells for the CPU tests: the real harness, generators,
readers and references on tiny models, built without BENCHMARK.json."""
from __future__ import annotations

import copy

from chipbench import harness

MODELS = {
    "dense": {"name": "qwen3-smoke", "family": "dense", "n_layers": 2,
              "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
              "d_ff": 128, "vocab_size": 256, "qk_norm": True,
              "rope_theta": 1000000.0, "norm_eps": 1e-06,
              "tie_embeddings": True},
    "ssm": {"name": "xlstm-smoke", "family": "ssm", "n_layers": 4,
            "d_model": 64, "n_heads": 2, "n_kv_heads": 2, "d_ff": 0,
            "vocab_size": 256, "slstm_every": 2, "norm_eps": 1e-06},
}

ENGINES = {
    "dense": {"kv_layout": "paged", "max_batch": 4, "cache_len": 128,
              "block_size": 16, "n_blocks": 33, "prefix_cache": True},
    "ssm": {"kv_layout": "dense", "max_batch": 4, "cache_len": 128},
}

TRAFFIC = {
    "chat": {"loop": "open_loop", "load": 0.8,
             "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 1.0,
                            "min": 4, "max": 48, "grid": 16},
             "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.8,
                            "min": 2, "max": 24},
             "drain_s": 30},
    "decode-batch": {"loop": "closed_loop", "clients_per_slot": 2,
                     "pool_per_client": 8,
                     "prompt_len": {"dist": "uniform", "min": 8, "max": 32,
                                    "grid": 8},
                     "output_len": {"dist": "lognormal", "median": 24,
                                    "sigma": 0.5, "min": 8, "max": 64},
                     "ramp_completions": 0.25},
}


CONFIGS = {"dense": "qwen3-0.6b", "ssm": "xlstm-350m"}
#: the metrics a smoke cell of each mix reports: (end-to-end, per-layer)
METRICS = {
    "chat": (["ttft_p50_ms", "ttft_p90_ms", "itl_p95_ms", "setup_s"], []),
    "decode-batch": (["itl_p95_ms", "output_tok_per_s", "setup_s"],
                     ["occupancy.batch", "step_mfu.batch",
                      "decode_hbm_roofline.batch",
                      "paged_decode_attn_roofline", "idle_share.batch"]),
}


def cell(family: str, mix: str, *, rate: float = 4.0,
         logit_gap: float = 1.0) -> harness.Cell:
    """The configuration file of ``family``'s benchmark model, with its
    sizes and engine arguments swapped for smoke ones, under ``mix``."""
    config = harness._json(harness.PKG / "configs"
                           / f"{CONFIGS[family]}.json")
    config["model"] = dict(MODELS[family])
    config["engine"] = dict(ENGINES[family])
    per_cell = {"sample_requests": 4, "logit_gap": logit_gap,
                "compared_tokens": 8}
    if mix == "chat":
        per_cell["traffic"] = {"rate_per_s": rate}
    e2e, per_layer = METRICS[mix]
    return harness.Cell(name=f"{CONFIGS[family]}.{mix}", chips=1,
                        config=config, traffic=copy.deepcopy(TRAFFIC[mix]),
                        cell=per_cell,
                        e2e=[{"name": n, "unit": "-"} for n in e2e],
                        per_layer=[{"name": n, "unit": "-"}
                                   for n in per_layer])
