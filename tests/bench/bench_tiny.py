"""Tiny configurations and mixes that drive a whole benchmark run on the
CPU: the system's smoke-size models through the same harness."""

import jax

import manifest
import run_cell

SYSTEM = {"overrides": {"param_dtype": "bfloat16", "tie_embeddings": True},
          "serve": {"max_batch": 4, "cache_len": 256, "page_size": 16},
          "smoke": True}

QWEN = {"name": "tiny_qwen", "model_type": "qwen2", "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-6, "rope_theta": 1e6, "tie_word_embeddings": True,
        "vocab_size": 512, "system": {"arch": "qwen25_3b", **SYSTEM}}

# Qwen2 with its own output head, as Qwen2 models above 3B ship
QWEN_UNTIED = {**QWEN, "name": "tiny_qwen_untied", "tie_word_embeddings": False,
               "system": {**QWEN["system"], "overrides": {
                   **SYSTEM["overrides"], "tie_embeddings": False}}}

STARCODER = {"name": "tiny_starcoder", "model_type": "starcoder2",
             "hidden_size": 64, "intermediate_size": 128,
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 2, "norm_epsilon": 1e-6,
             "rope_theta": 1e5, "use_bias": True, "tie_word_embeddings": True,
             "vocab_size": 512, "system": {"arch": "starcoder2_3b", **SYSTEM}}

LENGTHS = {"prompt": {"median": 40, "sigma": 0.5, "min": 16, "max": 96,
                      "grid": 8},
           "output": {"median": 8, "sigma": 0.3, "min": 4, "max": 16}}
BACKLOG = {"kind": "offline_backlog", "requests": 32, "block": 4,
           "open_after_completed": 4, **LENGTHS}

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "qwen25_3b.decode_long"


def _metrics(*names):
    return [{"name": n, "unit": "-"} for n in names]


# what the cell reports, by its readers in benchmarks/chip/metrics
METRICS = {
    False: _metrics("out_tokens_per_s", "itl_p95_ms", "setup_s"),
    True: _metrics("engine.step_wall_ms", "engine.batch_occupancy",
                   "kv_cache.pages_in_use", "model_step.decode_mfu",
                   "device.idle.offline")}


def run(conf=QWEN, mix=BACKLOG, seed=2**31 + 17, seconds=2.0, trace=False,
        fault=None, limits=None, control=None):
    """One run of the cell's harness on a tiny model, on the CPU."""
    import repro.core as core

    core.init(pools={"default": 2, "prefill": 2, "io": 1})
    w = {"name": CELL, "chips": 1}
    return run_cell.run(
        w, conf, mix, seed, seconds, trace, PEAKS, jax.devices()[:1],
        METRICS[trace],
        limits or {**manifest.limits(CELL), "tokens_compared": 20},
        run_cell.CompileCounter(), fault=fault, control=control)
