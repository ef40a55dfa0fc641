"""The check that decides ``correct``, driven through a whole run at a
tiny size on the CPU (the look for a chip skipped): a sound program
passes; the fp8 control and each fault a serving cell can have fail."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import tiny


def _alter_tokens(llm):
    """Every fifth emitted token is replaced where the engine makes it."""
    eng = llm.engine
    emit = eng._emit_token
    count = {"n": 0}

    def altered(i, req, tok, *rest):
        count["n"] += 1
        if count["n"] % 5 == 0:
            tok = (tok + 1) % eng.cfg.vocab_size
        return emit(i, req, tok, *rest)

    eng._emit_token = altered


@pytest.mark.parametrize("traffic", ["chat", "sessions"])
def test_sound_run_is_correct_and_control_is_not(traffic):
    r = tiny.run("qwen3-0.6b.chat", traffic, control=True)
    assert r["attempted"] > 0
    # the program's own reading of the same run passes ...
    assert r["program_served_gap"] <= tiny.LIMITS["served_gap"], r
    # ... and the fp8 control, put in its place, comes out not correct
    assert r["correct"] is False, r["checked"]
    assert r["checked"]["served_gap"]["value"] > tiny.LIMITS["served_gap"]
    assert list(r)[-1] == "checked"


def test_token_altered_where_produced_is_not_correct():
    r = tiny.run("qwen3-0.6b.chat", "chat", fault=_alter_tokens)
    assert r["correct"] is False
    assert r["checked"]["served_gap"]["value"] > 10 * tiny.LIMITS[
        "served_gap"]


_TP_SCRIPT = r"""
import json, sys
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1]]
import jax.numpy as jnp
from bench.tests import tiny
fault = None
if sys.argv[2] == "drop-exchange":
    from repro.core import reduced_softmax as rs
    # the cross-chip (val, idx) combine of the sharded head left out:
    # every chip keeps the first shard's candidate
    rs._combine_val_idx = lambda vals, idxs, axis=-1: (
        jnp.take(idxs, 0, axis=axis), jnp.take(vals, 0, axis=axis))
r = tiny.run("qwen3-0.6b.chat", "chat",
             config=dict(num_attention_heads=8, num_key_value_heads=4,
                         tie_word_embeddings=False, tp=4))
print(json.dumps({"correct": r["correct"], "checked": r["checked"],
                  "count": r["device"]["count"]}))
"""


@pytest.mark.parametrize("mode", ["sound", "drop-exchange"])
def test_tp4_run_sees_the_exchange_left_out(mode):
    """A tensor-parallel run (tp=4 over four CPU devices; no tp cell is in
    the benchmark yet) passes, and fails with the sharded head's
    cross-chip combine left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _TP_SCRIPT,
                           str(harness.ROOT), mode], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["count"] == 4
    assert out["correct"] is (mode == "sound"), out
