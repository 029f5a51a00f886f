"""Launcher plumbing shared by serve, train and chip_smoke.py
(``repro.launch.cli``): the ``--scale`` model choice, ``run_training``
taking a ``ModelConfig``, and where the persistent compile cache goes
(DESIGN.md §Compile cache).  Cache checks run in subprocesses: turning
the cache on is process-wide."""
import dataclasses
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get_model_config
from repro.data import tokenizer
from repro.launch import cli, serve
from repro.launch.train import run_training

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _python(code: str, **env) -> str:
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu", **env)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=full, timeout=120)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("scale", ["laptop", "pod"])
def test_model_config_scale(scale):
    cfg = cli.model_config("areal-qwen-1.5b", scale)
    published = get_model_config("areal-qwen-1.5b")
    if scale == "pod":
        assert cfg == published
        assert (cfg.n_layers, cfg.vocab_size) == (28, 151_936)
    else:
        assert cfg.vocab_size == tokenizer.VOCAB_SIZE
        assert cfg.n_layers < published.n_layers
    # the math tokenizer's ids are valid at either scale
    assert max(tokenizer.encode("123+456=579", bos=True, eos=True)) \
        < cfg.vocab_size
    with pytest.raises(ValueError):
        cli.model_config("areal-qwen-1.5b", "galaxy")


def test_serve_parser_takes_scale():
    assert serve.build_parser().parse_args([]).scale == "laptop"
    assert serve.build_parser().parse_args(["--scale", "pod"]).scale == "pod"


def test_run_training_takes_a_model_config():
    cfg = dataclasses.replace(cli.model_config("areal-qwen-1.5b", "laptop"),
                              n_layers=1, name="qwen-laptop-1l")
    ctl, trainer, _ = run_training(cfg, steps=1, batch_size=4,
                                   answers_per_prompt=2, n_slots=4,
                                   max_gen_len=4, final_eval=False)
    assert trainer.model.cfg is cfg
    assert trainer.version == 1 and len(ctl.history) == 1


def test_compile_cache_env_dir_is_used_as_is(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets nothing and
    the entries land in that directory."""
    cache = tmp_path / "cache"
    out = _python("""
        import jax, jax.numpy as jnp
        from repro.launch import cli
        where = cli.enable_compile_cache()
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
        print(where, jax.config.jax_compilation_cache_dir)
    """, JAX_COMPILATION_CACHE_DIR=str(cache),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert out.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_checkout_dir():
    """Without the variable the cache is the fixed <checkout>/.jax_cache
    (nothing is compiled here, so nothing is written to the checkout)."""
    out = _python("""
        import jax
        from repro.launch import cli
        print(cli.enable_compile_cache(), jax.config.jax_compilation_cache_dir)
    """)
    assert out.split() == [str(ROOT / ".jax_cache")] * 2
