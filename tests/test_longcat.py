"""LongCat-Flash-Chat at a small size on the CPU, in float32, with seeded
random weights: the plain reference (``repro.models.reference_longcat``)
against ``transformers``' ``LongcatFlashForCausalLM``, the program's
``longcat`` layer kind against the reference through prefill and batched
decode with a slot reused, and the held-share expert layer's shares
against the uncut layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduce_cfg
from repro.models import build_model
from repro.models import reference_longcat as ref
from repro.models.config import MLACfg, MoECfg
from repro.models.moe import held_moe_apply

#: published keys at a small size: 16 routed experts of which shares are
#: held, 8 identity experts, top-4 (so identity and routed choices mix)
C = {"hidden_size": 64, "num_layers": 2, "num_attention_heads": 4,
     "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
     "qk_rope_head_dim": 8, "v_head_dim": 16, "ffn_hidden_size": 96,
     "expert_ffn_hidden_size": 24, "n_routed_experts": 16,
     "zero_expert_num": 8, "moe_topk": 4, "routed_scaling_factor": 6.0,
     "rms_norm_eps": 1e-5, "rope_theta": 1e4, "vocab_size": 128,
     "mla_scale_q_lora": True, "mla_scale_kv_lora": True}


def program_cfg(n_held=0, held_start=0, n_layers=C["num_layers"]):
    c = C
    return reduce_cfg(ARCHS["longcat-flash-chat"].cfg).replace(
        n_layers=n_layers, d_model=c["hidden_size"],
        held_experts=n_held, held_start=held_start,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_attention_heads"], d_ff=c["ffn_hidden_size"],
        vocab=c["vocab_size"], rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], remat="none", dtype="float32",
        mla=MLACfg(
            q_lora=c["q_lora_rank"], kv_lora=c["kv_lora_rank"],
            rope_dim=c["qk_rope_head_dim"], nope_dim=c["qk_nope_head_dim"],
            v_dim=c["v_head_dim"], lora_scale=True),
        moe=MoECfg(n_experts=c["n_routed_experts"], top_k=c["moe_topk"],
                   d_expert=c["expert_ffn_hidden_size"],
                   n_zero=c["zero_expert_num"],
                   routed_scale=c["routed_scaling_factor"]))


def ref_weights(key, n_layers=C["num_layers"], n_experts=None):
    """Reference-layout weights: matrices N(0, 1/fan_in), norm scales
    1 + N(0, 0.1^2), a score-correction bias of the scores' own size."""
    c = C
    d, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    ql, kl, f, fe = (c["q_lora_rank"], c["kv_lora_rank"],
                     c["ffn_hidden_size"], c["expert_ffn_hidden_size"])
    E = c["n_routed_experts"] + c["zero_expert_num"]
    n = c["n_routed_experts"] if n_experts is None else n_experts
    keys = iter(jax.random.split(key, 512))

    def mat(*shape):
        return jax.random.normal(next(keys), shape) * shape[-2] ** -0.5

    def nrm(k):
        return 1.0 + 0.1 * jax.random.normal(next(keys), (k,))

    def attn():
        return {"q_a_proj": mat(d, ql), "q_a_layernorm": nrm(ql),
                "q_b_proj": mat(ql, H * (nope + rope)),
                "kv_a_proj_with_mqa": mat(d, kl + rope),
                "kv_a_layernorm": nrm(kl),
                "kv_b_proj": mat(kl, H * (nope + vd)),
                "o_proj": mat(H * vd, d)}

    def mlp(width):
        return {"gate_proj": mat(d, width), "up_proj": mat(d, width),
                "down_proj": mat(width, d)}

    layers = []
    for _ in range(n_layers):
        layers.append({
            "input_layernorm": [nrm(d), nrm(d)],
            "post_attention_layernorm": [nrm(d), nrm(d)],
            "self_attn": [attn(), attn()], "mlps": [mlp(f), mlp(f)],
            "classifier": mat(d, E),
            "e_score_correction_bias":
                jax.random.normal(next(keys), (E,)) / E,
            "experts": {"gate_proj": mat(n, d, fe), "up_proj": mat(n, d, fe),
                        "down_proj": mat(n, fe, d)}})
    return {"embed": jax.random.normal(next(keys), (c["vocab_size"], d)),
            "norm": nrm(d), "lm_head": mat(d, c["vocab_size"]),
            "layers": layers}


def _rope_perm(r):
    """The published interleaved rotary pairs (0,1), (2,3), ... as the
    program's halves: columns 0, 2, 4, ..., then 1, 3, 5, ..."""
    return np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2)])


def program_params(w, model):
    """Reference weights in the program's tree: matrices reshaped per
    head, and the rope columns of q and of the shared rope key permuted
    from interleaved pairs to halves (exact: q.k is unchanged when both
    are permuted alike)."""
    c = C
    d, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    kl = c["kv_lora_rank"]
    perm = np.concatenate([np.arange(nope), nope + _rope_perm(rope)])

    def layer(p):
        out = {}
        for i in (0, 1):
            a = p["self_attn"][i]
            kv_b = a["kv_b_proj"].reshape(kl, H, nope + vd)
            out[f"ln_attn{i}"] = {"w": p["input_layernorm"][i]}
            out[f"ln_mlp{i}"] = {"w": p["post_attention_layernorm"][i]}
            out[f"mla{i}"] = {
                "wq_a": a["q_a_proj"], "q_norm": a["q_a_layernorm"],
                "wq_b": a["q_b_proj"].reshape(-1, H, nope + rope)[..., perm],
                "wkv_a": a["kv_a_proj_with_mqa"][:, :kl],
                "kv_norm": a["kv_a_layernorm"],
                "wk_rope": a["kv_a_proj_with_mqa"][:, kl:][:,
                                                           _rope_perm(rope)],
                "wk_b": kv_b[..., :nope], "wv_b": kv_b[..., nope:],
                "wo": a["o_proj"].reshape(H, vd, d)}
            m = p["mlps"][i]
            out[f"mlp{i}"] = {"wg": m["gate_proj"], "wu": m["up_proj"],
                              "wd": m["down_proj"]}
        e = p["experts"]
        out["moe"] = {"router": p["classifier"],
                      "router_bias": p["e_score_correction_bias"],
                      "wg": e["gate_proj"], "wu": e["up_proj"],
                      "wd": e["down_proj"]}
        return out

    layers = [layer(p) for p in w["layers"]]
    ((unit, reps),) = model.segments
    seg = (jax.tree.map(lambda *a: jnp.stack(a), *layers) if reps > 1
           else layers[0])
    tree = {"embed": w["embed"], "final_norm": {"w": w["norm"]},
            "lm_head": w["lm_head"], "seg0": {"u0": seg}}
    assert (jax.tree.structure(tree)
            == jax.tree.structure(model.abstract_params()))
    return tree


# ----------------------------------------------- the reference against HF
def test_reference_matches_transformers():
    torch = pytest.importorskip("torch")
    mod = pytest.importorskip(
        "transformers.models.longcat_flash.modeling_longcat_flash")
    from transformers.models.longcat_flash.configuration_longcat_flash \
        import LongcatFlashConfig
    hf_cfg = LongcatFlashConfig(**{k: v for k, v in C.items()
                                   if not k.startswith("mla_scale")},
                                num_hidden_layers=2 * C["num_layers"],
                                head_dim=C["qk_rope_head_dim"])
    hf_cfg._attn_implementation = "eager"
    hf = mod.LongcatFlashForCausalLM(hf_cfg).float().eval()
    w = ref_weights(jax.random.PRNGKey(3))

    def t(a):                       # (in, out) -> nn.Linear (out, in)
        return torch.tensor(np.asarray(a).T.copy())

    sd = {"model.embed_tokens.weight": torch.tensor(np.asarray(w["embed"])),
          "model.norm.weight": torch.tensor(np.asarray(w["norm"])),
          "lm_head.weight": t(w["lm_head"])}
    for li, p in enumerate(w["layers"]):
        pre = f"model.layers.{li}."
        for i in (0, 1):
            sd[pre + f"input_layernorm.{i}.weight"] = torch.tensor(
                np.asarray(p["input_layernorm"][i]))
            sd[pre + f"post_attention_layernorm.{i}.weight"] = torch.tensor(
                np.asarray(p["post_attention_layernorm"][i]))
            for k, v in p["self_attn"][i].items():
                sd[pre + f"self_attn.{i}.{k}.weight"] = (
                    torch.tensor(np.asarray(v)) if v.ndim == 1 else t(v))
            for k, v in p["mlps"][i].items():
                sd[pre + f"mlps.{i}.{k}.weight"] = t(v)
        sd[pre + "mlp.router.classifier.weight"] = t(p["classifier"])
        sd[pre + "mlp.router.e_score_correction_bias"] = torch.tensor(
            np.asarray(p["e_score_correction_bias"]))
        for e in range(C["n_routed_experts"]):
            for k in ("gate_proj", "up_proj", "down_proj"):
                sd[pre + f"mlp.experts.{e}.{k}.weight"] = t(
                    p["experts"][k][e])
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    assert not unexpected and not missing, (missing, unexpected)
    toks = np.random.default_rng(0).integers(0, C["vocab_size"], 24)
    with torch.no_grad():
        want = hf(torch.tensor(toks[None])).logits[0].numpy()
    got = np.asarray(ref.forward(C, w, jnp.asarray(toks)))
    # both float32; torch and XLA order their sums differently
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max(),
                               rtol=0)


# ------------------------------------------ the program against the reference
def test_prefill_then_decode_with_a_reused_slot_matches_the_reference():
    """Three requests through the engine's own jitted prefill, splice and
    decode over two slots: A and B live, A done, C spliced into A's slot
    while B decodes on.  Each served position's logits against the
    reference's full forward pass over that request."""
    from repro.serve import ServeEngine
    cfg = program_cfg()
    eng = ServeEngine(cfg, slots=2, max_len=32)
    w = ref_weights(jax.random.PRNGKey(7))
    eng.params = program_params(w, eng.model)
    step = jax.jit(eng.model.decode_step)
    rng = np.random.default_rng(1)
    seqs = {r: list(rng.integers(0, C["vocab_size"], n))
            for r, n in (("A", 9), ("B", 10), ("C", 11))}
    fwd = jax.jit(lambda t: ref.forward(C, w, t))
    want = {r: np.asarray(fwd(jnp.asarray(s))) for r, s in seqs.items()}
    got = {r: [] for r in seqs}
    lens = {"A": 5, "B": 3, "C": 7}      # prompt lengths; the rest is fed

    def admit(r, slot):
        lg, pcache = eng._prefill(eng.params, {
            "tokens": jnp.asarray([seqs[r][:lens[r]]], jnp.int32)})
        got[r].append(np.asarray(lg)[0, -1])
        eng.caches = eng._splice(eng.caches, pcache, slot)

    def decode(live):                    # {slot: request}
        toks = np.zeros((2, 1), np.int32)
        pos = np.zeros((2, 1), np.int32)
        for s, r in live.items():
            p = lens[r] - 1 + len(got[r])
            toks[s, 0], pos[s, 0] = seqs[r][p], p
        lg, eng.caches = step(eng.params, eng.caches, jnp.asarray(toks),
                              jnp.asarray(pos))
        for s, r in live.items():
            got[r].append(np.asarray(lg)[s, 0])

    admit("A", 0)
    admit("B", 1)
    for _ in range(3):
        decode({0: "A", 1: "B"})
    admit("C", 0)                        # A is done: its slot is reused
    for _ in range(3):
        decode({0: "C", 1: "B"})
    for r, g in got.items():
        w_r = want[r][lens[r] - 1:lens[r] - 1 + len(g)]
        # float32 on both sides; the program's absorbed attention and its
        # expert weights applied before the down projection reorder sums
        np.testing.assert_allclose(np.stack(g), w_r,
                                   atol=2e-4 * np.abs(w_r).max(), rtol=0,
                                   err_msg=r)


def test_full_forward_matches_the_reference():
    cfg = program_cfg()
    model = build_model(cfg)
    w = ref_weights(jax.random.PRNGKey(11))
    params = program_params(w, model)
    toks = np.random.default_rng(2).integers(0, C["vocab_size"], (1, 20))
    x = model.embed(params, jnp.asarray(toks))
    pos = jnp.arange(20, dtype=jnp.int32)[None]
    h, _, _ = jax.jit(lambda p, x: model.forward(p, x, positions=pos))(
        params, x)
    got = np.asarray(model.logits(params, h))[0]
    want = np.asarray(ref.forward(C, w, jnp.asarray(toks[0])))
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max(),
                               rtol=0)


# ------------------------------------------------- the held shares
@pytest.mark.parametrize("shares", [2, 4])
def test_held_shares_sum_to_the_uncut_layer(shares):
    """Each share's part (its held experts for the rows routed to them,
    plus the identity experts' part that every chip computes alike):
    summed over the shares with the identity part counted once, they give
    the uncut layer, in the program and in the reference."""
    c = C
    n = c["n_routed_experts"]
    d = c["hidden_size"]
    p = ref_weights(jax.random.PRNGKey(5), n_layers=1)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 10, d))
    prog = {"router": p["classifier"],
            "router_bias": p["e_score_correction_bias"],
            "wg": p["experts"]["gate_proj"], "wu": p["experts"]["up_proj"],
            "wd": p["experts"]["down_proj"]}
    k = n // shares

    def share(start, count):
        cfg = program_cfg(n_held=count, held_start=start)
        sp = dict(prog, **{w: prog[w][start:start + count]
                           for w in ("wg", "wu", "wd")})
        return held_moe_apply(sp, x, cfg=cfg)

    uncut, rows = share(0, n)
    parts = [share(s * k, k) for s in range(shares)]
    # the identity part alone: the reference layer holding no expert
    none = dict(p, experts={e: v[:0] for e, v in p["experts"].items()})
    zero = jax.vmap(lambda xr: ref.moe(c, none, xr, 0))(x)
    total = sum(o for o, _ in parts) - (shares - 1) * zero
    np.testing.assert_allclose(total, uncut, atol=1e-5, rtol=1e-5)
    assert rows.shape == (x.shape[0], 2)
    np.testing.assert_array_equal(sum(r for _, r in parts)[:, 0], rows[:, 0])
    assert all((r[:, 1] == rows[:, 1]).all() for _, r in parts)
    # with every routed expert held, each token's choices are all counted
    assert (rows.sum(axis=1) == x.shape[1] * c["moe_topk"]).all()
    ref_uncut = jax.vmap(lambda xr: ref.moe(c, p, xr, 0))(x)
    np.testing.assert_allclose(uncut, ref_uncut, atol=1e-4, rtol=1e-4)
    ref_parts = [jax.vmap(lambda xr, s=s: ref.moe(
        c, dict(p, experts={e: v[s * k:(s + 1) * k]
                            for e, v in p["experts"].items()}), xr, s * k))(x)
        for s in range(shares)]
    np.testing.assert_allclose(sum(ref_parts) - (shares - 1) * zero,
                               ref_uncut, atol=1e-5, rtol=1e-5)


def test_decode_counts_the_expert_choices():
    """The serving step returns each row's choices that fell on held and
    on identity experts over every layer; the engine adds up the live
    rows', and a model that routes to no held expert returns None."""
    from repro.serve import ServeEngine
    cfg = program_cfg(n_held=4, held_start=4)
    eng = ServeEngine(cfg, slots=3, max_len=16)
    eng.warmup([4])
    eng.step([0, 1, 2])
    eng.step([0])
    held, zero = (int(v) for v in eng.moe_rows)
    assert held > 0 and zero > 0
    assert held + zero <= 4 * cfg.n_layers * C["moe_topk"]
    eng.warmup()
    assert list(eng.moe_rows) == [0, 0]
    other = ServeEngine(reduce_cfg(ARCHS["stablelm-1.6b"].cfg), slots=2,
                        max_len=16)
    other.step([0])
    assert list(other.moe_rows) == [0, 0]
