"""The FLOP and byte counts of each layer kind, against hand-computed
numbers for small configurations, and their weight bytes against the
program's own parameter count at full size."""
from __future__ import annotations

import json
import os

import pytest

import _chipbench
from chip.counts import attn, common, ssd

M_ATTN = _chipbench.TINY_ATTN    # d 64, 4 heads, 2 kv heads, hd 16, f 96
M_SSD = _chipbench.TINY_SSD      # d 64, d_in 128, 8 heads, N 16, K 4


def test_attn_prefill_by_hand():
    # matmul weights: q,k,v 64*(4+2+2)*16 = 8192, o 4*16*64 = 4096,
    # mlp 3*64*96 = 18432 -> 30720; + two layernorms (w, b) 256 -> 30976
    # causal pairs for 8 tokens: 36, each 4 * 4 heads * 16 = 256 flops
    flops, nbytes = attn.prefill(M_ATTN, 8)
    assert flops == 2 * 8 * 30720 + 36 * 256
    # weights once in bf16 + 8 new (k, v) of 2 heads x 16 in bf16
    assert nbytes == 30976 * 2 + 8 * (2 * 2 * 16 * 2)


def test_attn_decode_by_hand():
    flops, nbytes = attn.decode(M_ATTN, [5, 9])
    assert flops == 2 * 2 * 30720 + (5 + 9) * 256
    assert nbytes == 30976 * 2 + (5 + 9) * 128


def test_ssd_by_hand():
    # in_proj 64*(2*128 + 2*16 + 8) = 18944, out_proj 128*64 = 8192
    mat = 18944 + 8192
    # + conv 4*160 and its bias 160, a_log/dt_bias/D 3*8, gated norm 128,
    # pre-norm 64
    params = mat + 640 + 160 + 24 + 128 + 64
    state = 8 * 16 * 16                      # heads x N x head_dim
    per_tok = 2 * mat + 2 * 4 * 160 + 5 * state
    carried = state * 4 + 3 * 160 * 2        # f32 state + bf16 conv tail
    assert ssd.prefill(M_SSD, 8) == (8 * per_tok, params * 2 + carried)
    assert ssd.decode(M_SSD, [3, 4, 5]) == (3 * per_tok,
                                            params * 2 + 2 * 3 * carried)


def test_outside_layers_by_hand():
    flops, nbytes = common.outside(M_ATTN, 8, 1)
    assert flops == 2 * 64 * 256 + 4 * 64
    # 8 embedding rows, final layernorm (w, b), the 64 x 256 head, bf16
    assert nbytes == 8 * 64 * 2 + 128 * 2 + 64 * 256 * 2


@pytest.mark.parametrize("config", ["stablelm-1.6b", "mamba2-370m"])
def test_weight_bytes_match_the_program(config):
    """Decode bytes at no live slot are the weights read once: the
    program's parameter count in bf16 at full size, less an untied
    embedding table, of which only the rows looked up are read."""
    import jax
    import numpy as np
    from chip import harness
    from repro.models import build_model
    with open(os.path.join(_chipbench.ROOT, "benchmarks", "chip", "configs",
                           f"{config}.json")) as f:
        cfg_file = json.load(f)
    m = cfg_file["model"]
    model = build_model(harness.program_cfg(cfg_file))
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(model.abstract_params()))
    kind = {"attn": attn, "ssd": ssd}[m["pattern"][0]]
    layer_bytes = kind.decode(m, [])[1]
    outside = common.outside(m, 0, 0)[1]
    table = 0 if m["tie_embeddings"] else m["vocab"] * m["d_model"]
    assert m["n_layers"] * layer_bytes + outside == (n_params - table) * 2
