"""The LongCat-Flash configuration of the chip benchmark at a small size on
the CPU: its file against the program's ARCHS entry, the benchmark's
reference block (``layers/longcat.py``) against the program's plain
reference (``repro.models.reference_longcat``), the program against the
benchmark's reference through prefill and decode, the counts by hand, and
a window served through the harness, judged correct, with the held
experts' rows counted."""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _chipbench
from chip import harness, reference, weights
from chip.counts import longcat as counts

TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "d_ff": 96,
        "vocab": 256, "pattern": ["longcat"], "rope_theta": 10000.0,
        "norm": "rmsnorm", "norm_eps": 1e-5, "mlp": "gated_silu",
        "tie_embeddings": False, "dtype": "float32",
        "held_experts": 4, "held_start": 4,
        "mla_dims": {"q_lora": 32, "kv_lora": 16, "nope_dim": 16,
                     "rope_dim": 8, "v_dim": 16},
        "experts": {"n_routed": 16, "n_held": 4, "held_start": 4,
                    "n_zero": 8, "top_k": 4, "d_expert": 24,
                    "routed_scale": 6.0}}


def tiny_program_cfg(m):
    """The program's config for ``m``: the ARCHS entry cut as the file's
    keys say, with its MLA and expert sizes from ``m`` (the harness takes
    those from the ARCHS entry, at published size)."""
    from repro.configs import ARCHS
    from repro.models.config import MLACfg, MoECfg
    a, e = m["mla_dims"], m["experts"]
    cfg = ARCHS["longcat-flash-chat"].cfg.replace(
        n_kv_heads=m["n_heads"], remat="none",
        mla=MLACfg(lora_scale=True, **a),
        moe=MoECfg(n_experts=e["n_routed"], top_k=e["top_k"],
                   d_expert=e["d_expert"], n_zero=e["n_zero"],
                   routed_scale=e["routed_scale"]))
    return cfg.replace(**{k: tuple(v) if k == "pattern" else v
                          for k, v in m.items()
                          if k not in ("mla_dims", "experts")})


def ref_layout(m, w):
    """Canonical benchmark weights in ``reference_longcat``'s layout."""
    a = m["mla_dims"]
    kl, H = a["kv_lora"], m["n_heads"]
    layers = []
    for li in range(m["n_layers"]):
        p = jax.tree.map(lambda x: x[li], w["layers"][0])
        layers.append({
            "input_layernorm": [p["in_norm0"]["w"], p["in_norm1"]["w"]],
            "post_attention_layernorm": [p["post_norm0"]["w"],
                                         p["post_norm1"]["w"]],
            "self_attn": [{
                "q_a_proj": p[f"q_a{i}"], "q_a_layernorm": p[f"q_a_norm{i}"],
                "q_b_proj": p[f"q_b{i}"].reshape(a["q_lora"], -1),
                "kv_a_proj_with_mqa": p[f"kv_a{i}"],
                "kv_a_layernorm": p[f"kv_a_norm{i}"],
                "kv_b_proj": p[f"kv_b{i}"].reshape(kl, -1),
                "o_proj": p[f"o{i}"].reshape(H * a["v_dim"], -1)}
                for i in (0, 1)],
            "mlps": [{"gate_proj": p[f"gate{i}"], "up_proj": p[f"up{i}"],
                      "down_proj": p[f"down{i}"]} for i in (0, 1)],
            "classifier": p["router"],
            "e_score_correction_bias": p["router_bias"],
            "experts": {"gate_proj": p["expert_gate"],
                        "up_proj": p["expert_up"],
                        "down_proj": p["expert_down"]}})
    return {"embed": w["embed"], "norm": w["final_norm"]["w"],
            "lm_head": w["head"], "layers": layers}


def published(m):
    """``reference_longcat``'s config for ``m``."""
    a, e = m["mla_dims"], m["experts"]
    return {"hidden_size": m["d_model"], "num_attention_heads": m["n_heads"],
            "q_lora_rank": a["q_lora"], "kv_lora_rank": a["kv_lora"],
            "qk_nope_head_dim": a["nope_dim"],
            "qk_rope_head_dim": a["rope_dim"], "v_head_dim": a["v_dim"],
            "n_routed_experts": e["n_routed"], "zero_expert_num": e["n_zero"],
            "moe_topk": e["top_k"], "routed_scaling_factor": e["routed_scale"],
            "rms_norm_eps": m["norm_eps"], "rope_theta": m["rope_theta"],
            "mla_scale_q_lora": True, "mla_scale_kv_lora": True}


def test_config_file_agrees_with_the_arch_entry():
    from repro.configs import ARCHS
    with open(os.path.join(_chipbench.ROOT, "benchmarks", "chip", "configs",
                           "longcat-flash-chat.json")) as f:
        c = json.load(f)
    full = ARCHS["longcat-flash-chat"].cfg
    m, a, e = c["model"], c["model"]["mla_dims"], c["model"]["experts"]
    # published keys the cut leaves alone, against the program's full size
    for hf, ours in {"hidden_size": full.d_model,
                     "num_attention_heads": full.n_heads,
                     "ffn_hidden_size": full.d_ff,
                     "q_lora_rank": full.mla.q_lora,
                     "kv_lora_rank": full.mla.kv_lora,
                     "qk_nope_head_dim": full.mla.nope_dim,
                     "qk_rope_head_dim": full.mla.rope_dim,
                     "v_head_dim": full.mla.v_dim,
                     "expert_ffn_hidden_size": full.moe.d_expert,
                     "zero_expert_num": full.moe.n_zero,
                     "moe_topk": full.moe.top_k,
                     "routed_scaling_factor": full.moe.routed_scale,
                     "rms_norm_eps": full.norm_eps,
                     "rope_theta": full.rope_theta}.items():
        assert c[hf] == ours, hf
    assert c["mla_scale_q_lora"] and c["mla_scale_kv_lora"]
    assert full.mla.lora_scale
    # the cut: each reduced key from its published value
    assert c["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert (full.n_layers, full.moe.n_experts, full.vocab) == (28, 512,
                                                               131072)
    assert c["num_layers"] == m["n_layers"] == 4
    assert c["n_routed_experts"] == e["n_held"] == m["held_experts"] == 16
    assert c["vocab_size"] == m["vocab"] == full.vocab // 8
    # the model keys the benchmark reads, against the program's sizes
    assert a == {"q_lora": full.mla.q_lora, "kv_lora": full.mla.kv_lora,
                 "nope_dim": full.mla.nope_dim,
                 "rope_dim": full.mla.rope_dim, "v_dim": full.mla.v_dim}
    assert (e["n_routed"], e["n_zero"], e["top_k"], e["d_expert"],
            e["routed_scale"], e["held_start"]) == (
        full.moe.n_experts, full.moe.n_zero, full.moe.top_k,
        full.moe.d_expert, full.moe.routed_scale, m["held_start"])
    prog = harness.program_cfg(c)
    assert (prog.n_layers, prog.vocab, prog.held_experts, prog.held_start,
            prog.d_model, prog.d_ff, prog.norm_eps) == (
        4, 16384, 16, 0, 6144, 12288, 1e-5)
    assert prog.mla == full.mla and prog.moe == full.moe


def test_benchmark_block_matches_the_program_reference():
    from repro.models import reference_longcat
    canon = weights.canonical(TINY, 2**33 + 9)
    toks = np.random.default_rng(3).integers(0, TINY["vocab"], (1, 30))
    got = np.asarray(reference.forward(TINY, canon, jnp.asarray(toks)))[0]
    want = np.asarray(reference_longcat.forward(
        published(TINY), ref_layout(TINY, canon), jnp.asarray(toks[0]),
        held_start=TINY["experts"]["held_start"]))
    # two float32 references at the highest precision
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max(),
                               rtol=0)


def test_program_matches_the_benchmark_reference():
    from repro.models import build_model
    model = build_model(tiny_program_cfg(TINY))
    canon = weights.canonical(TINY, 12)
    params = weights.to_program(TINY, canon, model)
    prompt, more = 20, 5
    toks = np.random.default_rng(4).integers(
        0, TINY["vocab"], (1, prompt + more)).astype(np.int32)
    want = np.asarray(jax.jit(lambda w, t: reference.forward(TINY, w, t))(
        canon, jnp.asarray(toks)))[0, prompt - 1:]
    caches = model.init_cache(1, 32)
    lg, caches = jax.jit(model.prefill)(params, jnp.asarray(toks[:, :prompt]),
                                        caches)
    got = [np.asarray(lg)[0, -1]]
    step = jax.jit(model.decode_step)
    for i in range(more):
        p = prompt + i
        lg, caches = step(params, caches, jnp.asarray(toks[:, p:p + 1]),
                          jnp.full((1, 1), p, jnp.int32))
        got.append(np.asarray(lg)[0, -1])
    # both float32; the program's absorbed attention reorders sums
    np.testing.assert_allclose(np.stack(got), want,
                               atol=2e-4 * np.abs(want).max(), rtol=0)


def test_counts_by_hand():
    # MLA: q_a 64*32, q_b 32*4*24, kv_a 64*24, kv_b 16*4*32, o 4*16*64
    mla = 2048 + 3072 + 1536 + 2048 + 4096
    mat = 2 * mla + 2 * 3 * 64 * 96 + 64 * 24            # + router 64 x 24
    norms = 2 * (2 * 64 + 32 + 16)
    per_expert = 3 * 64 * 24
    hit = 4 * (1 - (1 - 4 / 24) ** 3)
    f, b = counts.decode(TINY, [5, 7, 9])
    pairs = 3 * 4 * 4 / 24
    zero_pairs = 3 * 4 * 8 / 24
    attn = 2 * 21 * (2 * 4 * (16 + 8) + 2 * 4 * 16)
    assert f == pytest.approx(2 * 3 * mat + attn + 2 * pairs * per_expert
                              + 2 * zero_pairs * 64)
    latent = 2 * (16 + 8) * 2
    assert b == pytest.approx((mat + norms + 24) * 2
                              + hit * per_expert * 2 + 21 * latent)
    f, b = counts.prefill(TINY, 6)
    attn = 2 * 21 * (2 * 4 * (16 + 8) + 2 * 4 * 16)
    hit = 4 * (1 - (1 - 4 / 24) ** 6)
    assert f == pytest.approx(2 * 6 * mat + attn
                              + 2 * (6 * 4 * 4 / 24) * per_expert
                              + 2 * (6 * 4 * 8 / 24) * 64)
    assert b == pytest.approx((mat + norms + 24) * 2
                              + hit * per_expert * 2 + 6 * latent)


def test_weight_bytes_match_the_program():
    """Decode bytes at one live slot whose step hits every held expert
    (rows large) are the weights read once: the program's parameter count
    at the cell's size, less the embedding table."""
    from repro.models import build_model
    with open(os.path.join(_chipbench.ROOT, "benchmarks", "chip", "configs",
                           "longcat-flash-chat.json")) as f:
        m = json.load(f)["model"]
    model = build_model(harness.program_cfg({"arch": "longcat-flash-chat",
                                             "model": m}))
    n = sum(int(np.prod(x.shape))
            for x in jax.tree.leaves(model.abstract_params()))
    layer_bytes = counts._dense(m)[1] * 2 + counts._experts(m, 10**6)[1]
    from chip.counts import common
    outside = common.outside(m, 0, 0)[1]
    total = m["n_layers"] * layer_bytes + outside
    assert total == pytest.approx((n - m["vocab"] * m["d_model"]) * 2)


def test_a_window_is_served_correctly_and_counts_held_rows(monkeypatch):
    monkeypatch.setattr(harness, "program_cfg", lambda c: tiny_program_cfg(
        c["model"]))
    mix = {"arrival": "poisson", "rate_rps": 20.0, "clients": 2,
           "prompt_lens": [8, 16], "prompt_weights": [1, 1],
           "max_new": [4, 12], "check_requests": 4}
    cfg = {"arch": "longcat-flash-chat", "model": TINY,
           "serving": {"slots": 4, "max_len": 32, "queue_bound": 8,
                       "reference_rows": 2}}
    per_layer = [{"name": "moe_held_rows_per_step", "unit": "rows"}]
    cell = harness.Cell("tiny.longcat", 1, cfg, mix, [], per_layer,
                        {"max_logit_gap": {"limit": 1e-3}})
    bench = harness.Bench(cell)
    bench.build(5)
    win = bench.window(5, 1.5)
    bench.release()
    correct, compared, rows, _ = harness.compare(cell, win, 5)
    assert correct, compared
    assert win.result["moe_held_rows"] > 0 and win.result["moe_zero_rows"] > 0
    run = harness.RunData(cell, win, 0.0, {"flops_bf16": 1.0,
                                           "hbm_bytes_per_s": 1.0})
    held = harness.metric_value("moe_held_rows_per_step", run)
    assert held == win.result["moe_held_rows"] / win.result["steps"] > 0
