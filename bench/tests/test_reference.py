"""The plain reference against the program at a tiny size on the CPU.

The program computes in the file's ``torch_dtype``; these tests set it
to float32, so both sides work on the same bfloat16 weight values in
float32 and differ by summation order alone: ~1e-6 on logits of order
1.  A wrong equation (a missing norm, rotary on the wrong half, a head
dropped) moves logits by ~0.1 or more, so the tolerance 1e-4 sits two
orders of magnitude from either.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, weights
from bench.tests import tiny

TOL = 1e-4


@pytest.fixture(scope="module")
def setup():
    conf = dict(tiny.config(), torch_dtype="float32")
    cfg = harness.model_config(conf)
    dims = harness.dims_of(conf)
    params = weights.make(dims, 2 ** 33 + 1)
    return cfg, dims, params


def test_prefill_logits_match_program_forward(setup):
    from repro.models import lm

    cfg, dims, params = setup
    toks = np.random.default_rng(1).integers(0, dims["vocab"], 300)
    logits, _ = lm.forward(params, cfg, {"tokens": jnp.asarray(toks)[None]})
    with jax.default_matmul_precision("highest"):
        h = reference.hidden(params, dims, jnp.asarray(toks))
        ref = h @ params["embed"].T.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(ref),
                               atol=TOL, rtol=0)


def test_paged_decode_through_engine_matches(setup):
    """Chunked prefill then paged decode through ServeEngine: every
    served token is the reference's best at its position."""
    from repro.serve.api import LLM
    from repro.serve.params import SamplingParams

    cfg, dims, params = setup
    llm = LLM(params, cfg, n_slots=4, max_len=256, block_size=16,
              num_blocks=64, chunk_size=16, eos_id=-1)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, dims["vocab"], n) for n in (5, 40, 77)]
    outs = llm.generate(prompts, SamplingParams(max_new_tokens=24))
    for p, o in zip(prompts, outs):
        gaps = reference.served_gaps(params, dims, p, list(o.token_ids))
        assert len(gaps) == 24
        assert gaps.max() <= TOL


def test_untied_head_and_gqa(setup):
    """An untied head and more query heads per key head take the same
    path as the program."""
    from repro.models import lm

    _, dims, _ = setup
    conf = dict(tiny.config(), torch_dtype="float32",
                tie_word_embeddings=False, num_attention_heads=8)
    cfg = harness.model_config(conf)
    d = harness.dims_of(conf)
    params = weights.make(d, 5)
    toks = np.arange(50) % d["vocab"]
    logits, _ = lm.forward(params, cfg, {"tokens": jnp.asarray(toks)[None]})
    with jax.default_matmul_precision("highest"):
        ref = reference.hidden(params, d, jnp.asarray(toks)) \
            @ params["lm_head"].astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(ref),
                               atol=TOL, rtol=0)


def test_control_is_lower_precision(setup):
    """The fp8 control moves the logits far more than rounding does."""
    _, dims, params = setup
    toks = np.random.default_rng(3).integers(0, dims["vocab"], 120)
    exact = reference._control_gaps(params, reference._frozen(dims),
                                    jnp.asarray(toks), None)
    fp8 = reference._control_gaps(params, reference._frozen(dims),
                                  jnp.asarray(toks), "fp8")
    assert float(jnp.max(exact)) == 0.0
    assert float(jnp.max(fp8)) > 100 * TOL


def test_layout_change_is_refused():
    dims = harness.dims_of(tiny.config())
    shape = jax.eval_shape(lambda: weights.make(dims, 0))
    weights.check_layout(shape, dims)
    shape["decoder"][0]["slot0"].pop("ln2")
    with pytest.raises(ValueError):
        weights.check_layout(shape, dims)
