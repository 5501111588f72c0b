"""``chip_smoke.py`` on the CPU: its engine and kernel phases at the reduced
config (kernels in interpret mode), and its refusal to run without a TPU."""

import dataclasses
import importlib.util
from pathlib import Path

import jax

from repro.configs import smoke_config
from repro.launch.serve import load_model

_SPEC = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(chip_smoke)


def test_serve_requests_at_reduced_config():
    cfg, params = load_model(chip_smoke.ARCH, smoke=True, seed=0)
    out = chip_smoke.serve_requests(cfg, params, n_requests=8,
                                    prompt_lens=(8, 40), new_tokens=4,
                                    max_seq=64)
    assert out["finished"] == 8
    assert out["fused_compiles"] <= out["fused_bucket_count"]
    assert out["first_logits_err"] <= out["first_logits_tol"]
    # float32 on one backend: the dense path gives the same greedy tokens
    assert out["dense_agree"] == out["dense_total"]


def test_kernel_check_at_reduced_bf16_shape():
    cfg = dataclasses.replace(smoke_config(chip_smoke.ARCH),
                              dtype="bfloat16")
    out = chip_smoke.check_kernels(cfg, batch=2, max_pages=4, page=8,
                                   chunk=8)
    assert 0.0 < out["decode_err"] <= out["tol"]
    assert 0.0 < out["prefill_err"] <= out["tol"]


def test_main_refuses_without_tpu(capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) == 1
    assert "no TPU found" in capsys.readouterr().err
