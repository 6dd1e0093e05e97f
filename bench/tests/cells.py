"""A cell of BENCHMARK.json shrunk to a size a CPU test can hold: same
driver, block and traffic shape; fewer layers, narrower widths, shorter
sequences and fewer clients."""
from __future__ import annotations

import dataclasses
import time

import jax

from bench import harness, peaks
from bench.run import Context

BENCH = harness.benchmark()

# Weights at 0.1 rather than the published 0.02: a model this narrow
# answers from the current token alone at 0.02, so that neither a cache
# fault nor the fp8 control moves a logit. The serving cells' vocabulary
# is wide enough for near ties.
SMALL_TRAIN = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=2, intermediate_size=128,
                   vocab_size=512, initializer_range=0.1)
SMALL_SERVE = dict(num_hidden_layers=4, hidden_size=128,
                   num_attention_heads=4, num_key_value_heads=2,
                   intermediate_size=256, vocab_size=8192,
                   initializer_range=0.1)


def shrink(cfg: dict, **sizes) -> dict:
    return dict(cfg, **sizes)


def small_program_config(cfg: dict):
    """The repo's ModelConfig at ``cfg``'s (test-sized) widths."""
    from repro.configs.registry import get_config
    return dataclasses.replace(
        get_config(cfg["arch"]), num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


def small_context(cell: str, seed: int, seconds: float = 1.0,
                  fault=None) -> Context:
    w = harness.cell_of(BENCH, cell)
    mix = harness.traffic_of(w["traffic"])
    if mix["driver"] == "train":
        cfg = shrink(harness.config_of(BENCH, w["config"]),
                           **SMALL_TRAIN)
        mix = dict(mix, seq_len=32, rows_per_chip=4)
    else:
        cfg = shrink(harness.config_of(BENCH, w["config"]),
                           **SMALL_SERVE)
        mix = dict(mix, clients=8,
                   serve=dict(mix["serve"], max_len=256),
                   prompt=dict(mix["prompt"], median=96, max=128),
                   output=dict(mix["output"], max=64))
    return Context(cell=cell, cfg=cfg, mc=small_program_config(cfg),
                   mix=mix, seed=seed, seconds=seconds, trace=False,
                   devs=jax.devices()[:1], peaks=peaks.PEAKS["TPU v5 lite"],
                   t_start=time.perf_counter(), fault=fault)


# Limits at the test sizes, set as the cells' are (between the program's
# largest reading and the control's or a fault's smallest) from CPU runs at
# these sizes: train program loss 0.0013, gradient 0.0039, change 0.022;
# fp8 control loss 0.024; half batch gradient 0.19; state unchanged 1.0.
# Serve program 0.053 (qwen2) and 0.038 (starcoder2); fp8 control 1.17
# and 0.47; faults 3.5 and more. A narrow model's small leaves make the
# change's gap larger than at full width, hence its own limits.
SMALL_LIMITS = {
    "train": {"checks": {"loss_gap": {"limit": 0.008},
                         "grad_norm_gap": {"limit": 0.05},
                         "update_norm_gap": {"limit": 0.2}}},
    "serve_closed": {"checks": {"logit_gap": {"limit": 0.2}}},
}


def limits(ctx: Context) -> dict:
    return SMALL_LIMITS[ctx.mix["driver"]]
