"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU.

The chip is described (a ``v5e:2x2`` topology), not attached: each case
lowers one kernel at qwen3-0.6b's published widths and compiles it with
the TPU compiler installed beside jax.  That compiler refuses what the
chip would refuse and interpret mode never sees: shape casts Mosaic
cannot lower, slices off the tiling, more scoped VMEM than a kernel may
use.  ``tpu_custom_call`` in the compiled text shows the kernel was
compiled for the chip, not interpreted.

The serving step itself is compiled at the chat cell's widest shape,
with ``jax.default_backend`` read as the chip's so the dispatch picks
what it picks on the chip: the in-place paged kernel.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the worker that runs this
file keeps it until it exits.  The persistent compilation cache is off
around these compiles — a program compiled for a described chip cannot
be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import fused_argmax_head as fah
from repro.kernels import fused_topk_head as ftk
from repro.kernels import paged_attention as pa
from repro.models import lm

CFG = get_config("qwen3-0.6b")
D, V = CFG.d_model, CFG.vocab_size
HQ, HKV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
SLOTS = 8                 # decode rows chip_smoke.py serves
BLOCK, NUM_BLOCKS = 16, 1024
TABLE = 128               # block-table columns: 2048 tokens / 16
# query widths of the paged kernel in chip_smoke.py: T = 1 decode, and
# every pow-2 window its chunked prefill (chunk_size 64) and draft
# windows (spec_k 4) pad to
PAGED_WIDTHS = (1, 2, 4, 8, 16, 32, 64)
BF16, I32 = jnp.bfloat16, jnp.int32
# the chat cell's widest step: 64 rows, tables of 256 blocks over a pool
# of 3,072 16-token blocks; one layer's dense K/V view of such a step
# (64 rows x 4,096 tokens x 8 heads x 128 x bf16, K and V) is 1 GiB
STEP_ROWS, STEP_TABLE, STEP_POOL = 64, 256, 3072
DENSE_VIEW_BYTES = 2 * STEP_ROWS * STEP_TABLE * BLOCK * HKV * HD * 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("b", [1, SLOTS])
def test_fused_argmax_head_compiles(one_chip, b):
    txt = _compiled_text(
        lambda h, w: fah.fused_argmax_head_with_value(h, w, interpret=False),
        one_chip, ((b, D), BF16), ((D, V), BF16))
    assert "tpu_custom_call" in txt


def test_fused_topk_head_compiles(one_chip):
    txt = _compiled_text(
        lambda h, w: ftk.fused_topk_head(h, w, 4, interpret=False),
        one_chip, ((SLOTS, D), BF16), ((D, V), BF16))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("t", [5, 8])
def test_fused_verify_head_compiles(one_chip, t):
    txt = _compiled_text(
        lambda h, w, c: ftk.fused_verify_head(h, w, c, interpret=False),
        one_chip, ((SLOTS, t, D), BF16), ((D, V), BF16),
        ((SLOTS, t - 1), I32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("t", PAGED_WIDTHS)
def test_paged_attention_compiles(one_chip, t):
    q = (SLOTS, HQ, HD) if t == 1 else (SLOTS, t, HQ, HD)
    pos = (SLOTS,) if t == 1 else (SLOTS, t)
    pool = ((NUM_BLOCKS, BLOCK, HKV, HD), BF16)
    txt = _compiled_text(
        lambda q, k, v, bt, p: pa.paged_attention(q, k, v, bt, p,
                                                  interpret=False),
        one_chip, (q, BF16), pool, pool, ((SLOTS, TABLE), I32), (pos, I32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("t", [1, 64])
def test_decode_step_reads_the_pool_in_place(one_chip, t, monkeypatch):
    """The serving step at the chat cell's widest shape, compiled for a
    v5e through the dispatch (the backend read as the chip's): the paged
    kernel is in the program and no dense K/V view is — its temporaries
    stay under one layer's view (the reference's gather needs ~2.2 GiB)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def on_chip(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=one_chip)

    params = jax.tree.map(
        lambda x: on_chip(x, BF16 if jnp.issubdtype(x.dtype, jnp.floating)
                          else None),
        jax.eval_shape(lambda: lm.init_params(CFG, jax.random.PRNGKey(0))))
    cache = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(
            (l.shape[0], STEP_POOL, BLOCK) + l.shape[3:], l.dtype,
            sharding=one_chip),
        jax.eval_shape(lambda p: lm.init_cache(p, CFG, 1, 4096), params))
    toks = jax.ShapeDtypeStruct((STEP_ROWS, t), I32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((STEP_ROWS,) if t == 1 else (STEP_ROWS, t),
                               I32, sharding=one_chip)
    btab = jax.ShapeDtypeStruct((STEP_ROWS, STEP_TABLE), I32,
                                sharding=one_chip)
    compiled = jax.jit(
        lambda p, x, c, q, b: lm.decode_step(p, CFG, x, c, q,
                                             block_tables=b)).lower(
        params, toks, cache, pos, btab).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < DENSE_VIEW_BYTES


def test_flash_attention_prefill_compiles(one_chip):
    s = 2048
    txt = _compiled_text(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           interpret=False),
        one_chip, ((1, HQ, s, HD), BF16), ((1, HKV, s, HD), BF16),
        ((1, HKV, s, HD), BF16))
    assert "tpu_custom_call" in txt
