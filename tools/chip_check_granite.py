#!/usr/bin/env python3
"""Granite 4.0-H at the published widths of the cell's share (10 of 40
layers, 9 of 72 experts, an eighth of the vocabulary) through
`tools/chip_check_nemotron_h.py`: prefill and decode through the cell's
cache against the plain reference, the reference in single bfloat16
passes, which must fail, the program's routing margins against the
reference's, and the device's peak of memory. The same arguments.

    python tools/chip_check_granite.py [--prompt 300] [--steps 200] [--seed 1] [--time]
    python tools/chip_check_granite.py --tiny          # a toy size, on the CPU
    python tools/chip_check_granite.py --control granite4hs_serve_closed64 \
        --seed <n> [--state-left-unchanged]   # the cell's own comparison,
                                              # which must come out not correct
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chip_check_nemotron_h import main  # noqa: E402

# layers mamba, attention, mamba (6 blocks); 4 of 8 experts held, top-3
TINY = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=3,
    layer_types=["mamba", "attention", "mamba"], num_attention_heads=4,
    num_key_value_heads=2, max_position_embeddings=512, mamba_n_heads=8,
    mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=16,
    num_local_experts=4, num_experts_per_tok=3, intermediate_size=32,
    shared_intermediate_size=64, published={"num_local_experts": 8})

if __name__ == "__main__":
    sys.exit(main(config_name="granite4_h_small_32b_a9b", tiny=TINY))
