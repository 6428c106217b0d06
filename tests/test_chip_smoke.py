"""CPU rehearsal of ``chip_smoke.py``: its serving and comparison
functions at reduced widths on the CPU, and its refusal to report success
without a TPU.  The chip itself is only asked for in ``main()``."""
import importlib
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

pytestmark = pytest.mark.timeout(600)


@pytest.fixture(scope="module")
def smoke():
    # importable by name: spawned rank processes unpickle its functions
    if str(ROOT) not in sys.path:
        sys.path.append(str(ROOT))
    return importlib.import_module("chip_smoke")


def test_serving_matches_sequential_server(smoke):
    out = smoke.serve_phase(reduced=True, seed=0, requests=6, max_len=512)
    seq = smoke.sequential_phase(reduced=True, seed=0, requests=6,
                                 max_len=512)
    tok = smoke.compare_tokens(out["result"]["records"], seq)
    assert tok["first_token_matches"] == 6
    # float32 on the CPU: the batch-8 and batch-1 programs agree exactly
    assert tok["token_agreement"] == 1.0


def test_compare_tokens_rejects_a_wrong_first_token(smoke):
    served = [{"id": 1, "tokens": [5, 6]}, {"id": 2, "tokens": [7, 8]}]
    seq = [{"id": 1, "tokens": [5, 9]}, {"id": 2, "tokens": [3, 8]}]
    with pytest.raises(smoke.SmokeFailure, match="1 of 2"):
        smoke.compare_tokens(served, seq)


def test_prefill_logits_match_cpu_reference_child(smoke, tmp_path):
    """The engine's prefill and batched decode logits, in both precisions,
    against the CPU reference child, with the control outside the bound."""
    vocab = smoke.model_cfg(reduced=True).vocab
    seqs = smoke.reference_sequences(0, vocab)
    assert seqs[1][:-1] == seqs[0]
    proc = smoke.start_cpu_reference(reduced=True, seed=0, seqs=seqs,
                                     workdir=str(tmp_path))
    bf16 = smoke.logits_phase(reduced=True, seed=0, max_len=512)
    f32 = smoke.logits_phase(reduced=True, seed=0, max_len=512, f32=True)
    ref = smoke.finish_cpu_reference(proc, str(tmp_path))
    for k in smoke.REFERENCES:
        assert ref[k].shape == (2, vocab)
    assert f32["prefill"].shape == bf16["decode"].shape == (vocab,)
    lg = smoke.check_logits(bf16, f32, ref)
    assert lg["f32 prefill"]["argmax_equal"]
    assert lg["f32 decode"]["argmax_equal"]
    assert lg["control"]["rel_rms"] > 10 * smoke.F32_BOUND
    # the serving decode program emits the argmax of the probed logits
    assert (bf16["decode_tokens"] == bf16["decode_argmax"]).all()


def test_compare_logits_rejects_other_weights(smoke):
    rng = np.random.default_rng(0)
    f32 = rng.normal(size=1000).astype(np.float32)
    smoke.compare_logits(f32 * (1 + 5e-3), f32, 1e-2, "prefill")  # passes
    with pytest.raises(smoke.SmokeFailure, match="relative RMS"):
        smoke.compare_logits(rng.normal(size=1000).astype(np.float32),
                             f32, 1e-2, "prefill")


@pytest.mark.parametrize("fault", ["bf16 decode", "float32 prefill",
                                   "control"])
def test_check_logits_fails_a_wrong_run_or_a_blind_control(smoke, fault):
    """A run off the reference in either precision fails the check, and so
    does a control the bound cannot tell from a sound run."""
    rng = np.random.default_rng(1)
    f32 = rng.normal(size=(2, 1000)).astype(np.float32)
    ref = {"f32": f32, "bf16": f32 * (1 + 1e-3), "f32_own": f32 * 2}
    other = rng.normal(size=1000).astype(np.float32)
    bf16_run = {"prefill": f32[0], "decode": f32[1], "control": other}
    f32_run = {"prefill": 2 * f32[0], "decode": 2 * f32[1],
               "control": other}
    smoke.check_logits(bf16_run, f32_run, ref)
    if fault == "bf16 decode":
        bf16_run["decode"] = other
    elif fault == "float32 prefill":
        f32_run["prefill"] = 2 * f32[0] * (1 + 5e-2)
    else:
        f32_run["control"] = 2 * f32[0]
    with pytest.raises(smoke.SmokeFailure, match=fault):
        smoke.check_logits(bf16_run, f32_run, ref)


def test_swap_layers_exchanges_only_the_two_layers(smoke):
    import jax.numpy as jnp
    params = {"embed": jnp.ones((3, 2)),
              "seg0": {"w": jnp.arange(8.0).reshape(4, 2)}}
    out = smoke.swap_layers(params, 1)
    assert (out["seg0"]["w"] == jnp.asarray(
        [[0, 1], [4, 5], [2, 3], [6, 7]], jnp.float32)).all()
    assert (out["embed"] == 1).all()
    with pytest.raises(smoke.SmokeFailure, match="no stacked layer"):
        smoke.swap_layers({"embed": jnp.ones(2)}, 0)


def test_script_refuses_a_cpu_platform():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          capture_output=True, text=True, timeout=300,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stdout


def test_four_chip_trainer_matches_inproc_on_cpu(smoke):
    """The --four-chips comparison at reduced widths: socket trainer, one
    process per (would-be) chip, against the in-proc reference."""
    info = smoke.four_chip_phase(0, tpu=False, reduced=True, steps=2)
    assert info["platform"] == "cpu"
