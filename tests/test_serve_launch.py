"""``repro.launch.serve``: the published config without ``--smoke``, the
refusal of archs the paged, fused path cannot serve, and the engine's
warning when a config falls back from the requested paged path."""

import dataclasses

import jax
import pytest

from repro.configs import get_config, smoke_config
from repro.launch import serve
from repro.models import transformer as T

REFUSED = {"deepseek-v3-671b": "mla", "hymba-1.5b": "recurrent",
           "xlstm-350m": "GQA only", "hubert-xlarge": "encoder-only",
           "internvl2-1b": "vision_stub"}


@pytest.mark.parametrize("arch", sorted(REFUSED))
def test_load_model_refuses_unservable_arch(arch):
    with pytest.raises(ValueError, match=REFUSED[arch]):
        serve.load_model(arch, smoke=False, seed=0)


def test_cli_exits_with_the_reason(monkeypatch, capsys):
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    with pytest.raises(SystemExit) as e:
        serve.main(["--arch", "deepseek-v3-671b"])
    assert e.value.code == 2
    assert "cannot be served" in capsys.readouterr().err


def test_published_config_without_smoke(monkeypatch):
    """No ``--smoke``: the published width in bf16, weights from the seed
    (shapes only here; init at full width is the chip's job)."""
    init = T.init_params
    monkeypatch.setattr(T, "init_params", lambda cfg, key: jax.eval_shape(
        lambda: init(cfg, key)))
    cfg, params = serve.load_model("qwen1.5-0.5b", smoke=False, seed=0)
    assert cfg == get_config("qwen1.5-0.5b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (24, 1024, 16, 16, 64, 151936)
    assert cfg.dtype == "bfloat16"
    assert params["embed"].shape == (151936, 1024)


def test_engine_warns_on_paged_fallback():
    cfg = dataclasses.replace(smoke_config("qwen1.5-0.5b"), sliding_window=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.warns(RuntimeWarning, match="sliding-window"):
        eng = serve.build_engine(cfg, params, max_seq=32, max_batch=2)
    assert not eng.use_paged and not eng.use_fused
