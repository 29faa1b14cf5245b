"""``chip_smoke.py``'s phases at smoke size on the CPU (kernels interpreted).

The script itself runs only on a TPU; these tests import its phase
functions and steer them to shapes the CPU can run, so a broken path is
found here before it costs chip time.
"""
import importlib.util
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


def _smoke(arch):
    from repro.configs import get_config, smoke_config
    return smoke_config(get_config(arch))


def test_serve_phase_at_smoke_size():
    res = chip_smoke.serve_phase(_smoke("olmo-1b"), n_requests=3,
                                 prompt_range=(12, 40), max_new=4,
                                 prefill_chunk=16, n_slots=2)
    assert chip_smoke.check_serve(res, on_chip=False) == []
    assert res["tokens_emitted"] == 12 and res["completed"] == 3
    assert res["engine_first_token_matches_replay"]
    # interpreted kernels leave no Mosaic custom call behind
    assert not res["kernel_in_burst"] and not res["kernel_in_prefill_chunk"]
    assert chip_smoke.check_serve(res, on_chip=True) != []


def test_train_phase_at_smoke_size():
    res = chip_smoke.train_phase(_smoke("bert-base"), steps=2,
                                 global_batch=2, seq=32)
    assert chip_smoke.check_train(res, 2) == []
    assert res["state_bytes_per_device"] == [res["state_bytes"]]


@pytest.mark.parametrize("field,value", [
    ("quarantines", 1), ("fp32_retries", 1), ("completed", 2),
    ("tokens_emitted", 7), ("finite", False), ("logit_rms_diff", 1.0)])
def test_check_serve_flags_each_failure(field, value):
    ok = {"requests": 3, "completed": 3, "tokens_emitted": 12,
          "tokens_expected": 12, "quarantines": 0, "fp32_retries": 0,
          "finite": True, "logit_rms_diff": 0.01,
          "logit_ref_std": 10.0, "kernel_in_burst": True,
          "kernel_in_prefill_chunk": True}
    assert chip_smoke.check_serve(ok, on_chip=True) == []
    assert len(chip_smoke.check_serve(dict(ok, **{field: value}),
                                      on_chip=True)) == 1


def test_planted_attention_fault_fails_the_logit_check(monkeypatch):
    """Drop each chunk lane's last visible KV page from the kernel's mask:
    the replayed logits must then fail the check the correct kernel
    passes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import ServeConfig
    from repro.kernels import ops
    from repro.models import build_model
    from repro.models.layers import unbox
    from repro.serve import engine
    cfg = _smoke("olmo-1b").with_(softmax_impl="hyft16")
    params = jax.jit(lambda k: unbox(build_model(cfg).init(k)))(
        jax.random.PRNGKey(0))
    prompt = np.random.default_rng(0).integers(0, cfg.vocab, 36).astype(
        np.int32)
    scfg = ServeConfig(max_len=45, cache_dtype="fp2fx8", attn_mode="kernel",
                       n_slots=2, kv_layout="paged", page_size=16,
                       prefill_chunk=16)

    def logits(mode):
        return chip_smoke._first_step_logits(
            build_model(cfg.with_(attn_mode=mode)), params, scfg, prompt)

    ref = logits("unfused")
    good = chip_smoke.logit_gap(logits("kernel"), ref)
    verify = ops.flash_hyft_verify

    def drop_last_page(q, k, v, m, hcfg, **kw):
        L = m.shape[-1]
        last = L - 1 - jnp.argmax(m[..., ::-1] > 0, axis=-1)
        start = (last // k.shape[2] * k.shape[2])[..., None]
        return verify(q, k, v, jnp.where(jnp.arange(L) >= start, 0.0, m),
                      hcfg, **kw)

    monkeypatch.setattr(ops, "flash_hyft_verify", drop_last_page)
    monkeypatch.setattr(engine, "_CHUNK_CACHE", {})
    bad = chip_smoke.logit_gap(logits("kernel"), ref)
    tol = chip_smoke.LOGIT_TOL
    assert good["logit_rms_diff"] / good["logit_ref_std"] <= tol
    assert bad["logit_rms_diff"] / bad["logit_ref_std"] > tol


def test_check_train_flags_nonfinite_and_short():
    assert chip_smoke.check_train({"losses": [1.0, 2.0]}, 2) == []
    assert chip_smoke.check_train({"losses": [1.0, float("nan")]}, 2)
    assert chip_smoke.check_train({"losses": [1.0]}, 2)


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 1
    assert capsys.readouterr().out == ""


def test_refuses_outside_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", alone)
    r = subprocess.run([sys.executable, str(alone)], capture_output=True,
                       text=True, timeout=60, cwd=tmp_path)
    assert r.returncode != 0 and r.stdout == ""
