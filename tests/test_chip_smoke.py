"""``chip_smoke.py`` off the chip, and where entry points keep the
persistent compilation cache.

  - ``--tiny`` rehearses every phase in-process at smoke widths (Pallas
    in interpret mode) and still ends non-zero without the ok line: a
    rehearsal is not a chip smoke;
  - with no TPU and no ``--tiny`` the script refuses before building the
    full-width model;
  - every token must be the plain forward's argmax or within a bf16
    near tie of it, and runs may part only where Theorem 1 does not make
    the logits identical;
  - ``compile_cache.enable()`` leaves a set ``JAX_COMPILATION_CACHE_DIR``
    alone and writes there; otherwise it points JAX at
    ``<repo>/.jax_cache``; importing the package turns no cache on.
"""
import importlib
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _chip_smoke(monkeypatch):
    monkeypatch.syspath_prepend(str(REPO))
    return importlib.import_module("chip_smoke")


def test_tiny_rehearsal_runs_every_phase_and_fails(monkeypatch, capsys):
    rc = _chip_smoke(monkeypatch).main(["--tiny"])
    out = capsys.readouterr().out
    assert rc != 0
    for phase in "abcde":
        assert re.search(rf"^phase {phase} \(.*\): done ", out, re.M), out
        assert re.search(rf"^phase {phase}\b.* vs plain forward: ", out,
                         re.M), out
    assert "MISMATCH" not in out
    assert '"ok": true' not in out


def test_no_tpu_refuses_before_building_the_model(monkeypatch, capsys):
    rc = _chip_smoke(monkeypatch).main([])
    cap = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in cap.err
    assert cap.out == ""


def test_comparison_admits_only_bf16_near_ties(monkeypatch, capsys):
    """A token passes only as the plain forward's argmax or within
    NEAR_TIE of it; a divergence between runs is printed with the plain
    forward's margin there, and fails only under exact."""
    import numpy as np

    cs = _chip_smoke(monkeypatch)
    from repro.configs import get_config, smoke_config

    run = cs.Run(smoke_config(get_config("qwen3-0.6b")), None, cs.TINY, 0)
    logits = np.zeros(16, np.float32)
    logits[[9, 4, 3, 6, 7]] = [5.0, 5.0 - cs.NEAR_TIE / 2,
                               5.0 - cs.NEAR_TIE * 2, 1.0, 1.0]

    def forward(params, seq, n):            # the same logits everywhere
        return np.tile(logits, (seq.shape[1] - n, 1))

    monkeypatch.setattr(run, "_forward", forward)
    want = [[9, 9], [9, 9, 9]]
    run.check_plain("argmax", want)
    run.compare("same", want, [list(w) for w in want], exact=True)
    tie = [[9, 9], [9, 4, 9]]
    run.check("tie", want, tie)
    with pytest.raises(cs.Mismatch):
        run.compare("exact", want, tie, exact=True)
    with pytest.raises(cs.Mismatch):
        run.check_plain("wide", [[9, 9], [9, 9, 3]])
    with pytest.raises(cs.Mismatch):
        run.compare("short", want, tie[:1])
    out = capsys.readouterr().out
    assert "argmax vs plain forward: 5 of 5 tokens its argmax" in out
    assert ("parted tie vs a: request 1 (prompt" in out
            and "first differs at generated token 1: reference 9 vs 4; "
                "plain forward top-2 there ids 9, 4, logit margin" in out)
    assert "tie vs plain forward: 4 of 5 tokens its argmax, 1 within" in out
    assert "MISMATCH exact: request 1" in out
    assert "MISMATCH wide: request 1 (prompt" in out
    assert "generated token 2 is 3" in out


def _python(code: str, **env) -> str:
    e = {k: v for k, v in os.environ.items()
         if k != "JAX_COMPILATION_CACHE_DIR"}
    e.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu", **env)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=e, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout.strip()


def test_compile_cache_uses_the_deployment_directory(tmp_path):
    cache = tmp_path / "cache"
    out = _python("""
        import jax, jax.numpy as jnp
        from repro.launch import compile_cache
        print(compile_cache.enable())
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
        print(jax.config.jax_compilation_cache_dir)
    """, JAX_COMPILATION_CACHE_DIR=str(cache),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert out.splitlines() == [str(cache), str(cache)]
    assert any(cache.iterdir())             # written there


def test_compile_cache_defaults_to_repo_dir_and_is_off_on_import():
    out = _python("""
        import jax
        import repro.launch.serve, repro.serve.api, repro.serve.router
        print(jax.config.jax_compilation_cache_dir)
        from repro.launch import compile_cache
        print(compile_cache.enable())
        print(jax.config.jax_compilation_cache_dir)
    """)
    assert out.splitlines() == ["None", str(REPO / ".jax_cache"),
                                str(REPO / ".jax_cache")]
