"""A cell of BENCHMARK.json shrunk to the program's smoke configuration,
for runs of the harness on the CPU."""

from __future__ import annotations

import run as harness

#: hyperparameter name in the configuration file -> the program's field
QWEN3 = {"hidden_size": "d_model", "intermediate_size": "d_ff",
         "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim"}
MAMBA2 = {"d_model": "d_model", "n_layer": "n_layers",
          "padded_vocab_size": "vocab", "d_state": "ssm_state",
          "expand": "ssm_expand", "d_conv": "conv_width"}
#: enough served tokens (about 150 compared) that the control's widest gap
#: shows, as it does at a cell's own size
MIX = {"requests_per_batch": 8, "prompt_len": [8, 16], "levels": 2,
       "new_tokens": [16, 24], "max_context": 48, "check_requests": 8}


def smoke_config(config: dict) -> dict:
    """A configuration file's contents with its sizes shrunk to the
    program's smoke configuration.  The published vocabulary lies as many
    rows below the program's table as the file states
    (``padded_vocab_size - vocab_size``, none without it).  Program fields
    that the smoke size shrinks take the smoke values; the rest stay as the
    file states them, so that a departure shows here as it does at full
    size."""
    from repro.configs import get_config, get_smoke_config
    config = dict(config)
    full = get_config(config["program"])
    cfg = get_smoke_config(config["program"])
    padding = (config.get("padded_vocab_size", config["vocab_size"])
               - config["vocab_size"])
    names = QWEN3 if config["reference"] == "qwen3" else MAMBA2
    for key, field in names.items():
        config[key] = getattr(cfg, field)
    config["vocab_size"] = cfg.vocab - padding
    if config["reference"] == "mamba2":
        config["headdim"] = cfg.ssm_expand * cfg.d_model // cfg.ssm_heads
    config["program_fields"] = {
        k: (getattr(cfg, k, None)
            if getattr(cfg, k, None) != getattr(full, k, None) else v)
        for k, v in config["program_fields"].items()}
    return config


def smoke_files(workload: str) -> dict:
    files = harness.resolve(workload)
    mix = dict(files["mix"], **MIX)
    if mix["start_slice_gb"] < 16:
        mix["start_slice_gb"] = 1e-4
    return dict(files, config=smoke_config(files["config"]), mix=mix)


def program_config(config: dict):
    """The program's smoke configuration for a configuration file."""
    from repro.configs import get_smoke_config
    return get_smoke_config(config["program"])
