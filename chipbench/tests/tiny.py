"""Tiny stand-ins for the cells, for the CPU tests: the same harness, the
same dense GQA family, widths small enough for the CPU."""

TINY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256,
}

SERVE = {
    "server": {"max_batch": 4, "max_seq": 96, "max_new_tokens": 32},
    "traffic": {
        "generator": "open_loop", "schedule_seed": 7, "rate_per_s": 12.0,
        "preroll_s": 0.3, "grace_s": 30.0,
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                   "min": 16, "max": 48, "round_up_to": [16, 32, 48]},
        "output": {"dist": "uniform", "min": 8, "max": 24},
    },
    # from CPU readings at this size over six seeds (196 tokens checked
    # each): bf16 program 0-0.042, the fp8 control 0.17-1.04
    "check": {"requests": 16, "served_logit_gap": 0.1},
}


def overrides(sliding_window=None, **extra):
    cfg = dict(TINY_CONFIG, sliding_window=sliding_window)
    return {"config": cfg, "workload": dict(SERVE), **extra}
