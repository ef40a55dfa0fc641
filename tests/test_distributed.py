"""Distribution tests.

Metadata-level: sharding specs of every arch divide the production meshes
(no devices needed — AbstractMesh). Process-level: subprocess with 8
host devices runs real pjit train/decode steps, the EP MoE, the reduced
head's distributed argmax, and a small dry-run cell.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.launch import steps as steps_mod
from repro.models import api
from repro.optim import optimizer as opt_mod
from repro.parallel import sharding

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Metadata: every param/batch/cache spec divides the production meshes
# ---------------------------------------------------------------------------
def _abstract_mesh(multi_pod):
    from jax.sharding import AbstractMesh
    if multi_pod:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def _check_divisible(tree, specs, mesh, where):
    leaves = jax.tree.leaves(tree)
    specs_l = jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    assert len(leaves) == len(specs_l), where
    for leaf, spec in zip(leaves, specs_l):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % size == 0, (where, leaf.shape, spec)


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("multi_pod", [False, True])
def test_specs_divide_production_mesh(arch, multi_pod):
    cfg = get_config(arch)
    mesh = _abstract_mesh(multi_pod)
    params = api.params_struct(cfg)
    pspecs = sharding.param_specs(params, mesh)
    _check_divisible(params, pspecs, mesh, f"{arch} params")
    opt_cfg = opt_mod.AdamWConfig()
    opt = jax.eval_shape(lambda p: opt_mod.init_state(opt_cfg, p), params)
    ospecs = sharding.opt_state_specs(opt, pspecs)
    _check_divisible(opt, ospecs, mesh, f"{arch} opt")
    for sname, shape in SHAPES.items():
        if not shape_applicable(cfg, shape)[0]:
            continue
        b = api.batch_struct(cfg, shape)
        bspecs = sharding.batch_specs(b, mesh, shape.global_batch)
        _check_divisible(b, bspecs, mesh, f"{arch} {sname} batch")
        if shape.kind == "decode":
            cache = api.cache_struct(params, cfg, shape.global_batch,
                                     shape.seq_len)
            cspecs = sharding.cache_specs(cache, mesh, shape.global_batch)
            _check_divisible(cache, cspecs, mesh, f"{arch} {sname} cache")


def test_embedding_is_vocab_sharded():
    cfg = get_config("qwen3-32b")
    mesh = _abstract_mesh(False)
    specs = sharding.param_specs(api.params_struct(cfg), mesh)
    assert tuple(specs["embed"]) == ("model", "data")
    assert tuple(specs["lm_head"]) == ("data", "model")


# ---------------------------------------------------------------------------
# Subprocess: 8 fake host devices, real execution
# ---------------------------------------------------------------------------
def _run_sub(body: str) -> str:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        import dataclasses
        from repro.configs import ARCHS, smoke_config
        from repro.configs.base import ShapeSpec
        from repro.launch import mesh as mesh_mod, steps, hlo_stats
        from repro.optim.optimizer import AdamWConfig
        from repro.parallel import env, sharding
    """) + textwrap.dedent(body)
    env_ = dict(os.environ,
                PYTHONPATH=str(REPO / "src"),
                XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", script], env=env_,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_pjit_train_step_runs_8dev():
    out = _run_sub("""
        from repro.launch.train import train
        cfg = smoke_config(ARCHS["qwen3-0.6b"])
        mesh = mesh_mod.make_mesh((4, 2), ("data", "model"))
        shape = ShapeSpec("t", 32, 8, "train")
        state, losses = train(cfg, shape, AdamWConfig(lr=1e-3,
            warmup_steps=2, total_steps=10), mesh=mesh, steps=8,
            log=lambda *a, **k: None)
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0] + 0.1
        print("LOSSES", losses[0], losses[-1])
    """)
    assert "LOSSES" in out


def test_distributed_reduced_head_matches_local():
    out = _run_sub("""
        from repro.core import sharded_reduced_head, distributed_argmax
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = mesh_mod.make_mesh((2, 4), ("data", "model"))
        key = jax.random.PRNGKey(0)
        h = jax.random.normal(key, (16, 64))
        w = jax.random.normal(jax.random.fold_in(key, 1), (64, 512))
        got = sharded_reduced_head(h, w, mesh)
        want = jnp.argmax(h @ w, axis=-1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # distributed_argmax on sharded logits
        logits = jax.random.normal(key, (16, 512))
        got2 = distributed_argmax(logits, mesh, "model",
                                  batch_axes=("data",))
        np.testing.assert_array_equal(np.asarray(got2),
                                      np.asarray(jnp.argmax(logits, -1)))
        print("HEAD OK")
    """)
    assert "HEAD OK" in out


def test_sharded_engine_8dev_matches_local_and_ties():
    """The vocab-sharded reduced head through the SERVING ENGINE on 8
    devices: generations match the local engine, and an exact logit tie
    spanning two vocab SHARDS resolves to the lowest global index."""
    out = _run_sub("""
        from repro.models import lm
        from repro.serve.engine import Request, ServeEngine
        cfg = smoke_config(ARCHS["qwen3-0.6b"])
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        # exact tie between vocab ids 10 (shard 0) and 200 (shard 6)
        w = np.array(lm.lm_head_weight(params, cfg))
        w[:, 200] = w[:, 10]
        params["embed"] = jnp.asarray(w.T)        # qwen3 ties embeddings
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=7).astype(np.int32)
                   for _ in range(3)]

        def serve(head_mode, mesh):
            eng = ServeEngine(params, cfg, n_slots=2, max_len=32, eos_id=1,
                              head_mode=head_mode, mesh=mesh)
            reqs = [Request(i, p.copy(), 4) for i, p in enumerate(prompts)]
            for r in reqs:
                eng.submit(r)
            eng.run()
            return [r.generated for r in reqs]

        mesh = mesh_mod.make_host_mesh(model=8)   # all devices on 'model'
        got = serve("sharded", mesh)
        want = serve("reduced", None)
        assert got == want, (got, want)
        assert all(200 not in g for g in got), got
        print("SHARDED ENGINE OK")
    """)
    assert "SHARDED ENGINE OK" in out


def test_moe_ep_8dev_matches_oracle():
    out = _run_sub("""
        from repro.models.layers import moe_layer, init_moe
        cfg = smoke_config(ARCHS["phi3.5-moe-42b-a6.6b"])
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
        key = jax.random.PRNGKey(0)
        p = init_moe(key, cfg)
        x = jax.random.normal(key, (8, 16, cfg.d_model), jnp.float32)
        y0, _ = moe_layer(p, x, cfg, impl="oracle")
        mesh = mesh_mod.make_mesh((2, 4), ("data", "model"))
        with env.use_mesh(mesh):
            y1, _ = jax.jit(lambda pp, xx: moe_layer(pp, xx, cfg,
                                                     impl="ep"))(p, x)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                                   rtol=2e-4, atol=2e-5)
        print("EP OK")
    """)
    assert "EP OK" in out


def test_decode_step_8dev_seq_sharded_cache():
    out = _run_sub("""
        cfg = smoke_config(ARCHS["qwen3-0.6b"])
        mesh = mesh_mod.make_mesh((2, 4), ("data", "model"))
        shape = ShapeSpec("d", 64, 8, "decode")
        lo = steps.lower_decode(cfg, mesh, shape)
        compiled = lo.compile()
        txt = compiled.as_text()
        coll = hlo_stats.collective_bytes(txt)
        print("DECODE COLL", sorted(coll))
    """)
    assert "DECODE COLL" in out


def test_dryrun_small_cell():
    out = _run_sub("""
        os.environ["REPRO_XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        from repro.launch.dryrun import run_cell
        r = run_cell("qwen3-0.6b", "train_4k", "4x2")
        assert "totals" in r, r
        assert r["totals"]["flops_per_dev"] > 0
        assert r["useful_flops_ratio"] and r["useful_flops_ratio"] > 0.1
        assert r["full"]["fits_v5e_16g"] in (True, False)
        print("CELL OK", r["totals"]["bottleneck"])
    """)
    assert "CELL OK" in out


def test_train_resume_determinism(tmp_path):
    """Fault-tolerance invariant: preempt-at-k + restore == uninterrupted.

    (Bitwise on CPU: same data, same step function, donated buffers.)"""
    out = _run_sub(f"""
        from repro.launch.train import train
        cfg = smoke_config(ARCHS["qwen3-0.6b"])
        mesh = mesh_mod.make_mesh((4, 2), ("data", "model"))
        shape = ShapeSpec("t", 32, 8, "train")
        opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=12)
        quiet = lambda *a, **k: None
        _, full = train(cfg, shape, opt, mesh=mesh, steps=10, log=quiet)
        d = r"{tmp_path}"
        _, first = train(cfg, shape, opt, mesh=mesh, steps=5,
                         ckpt_dir=d, ckpt_every=5, log=quiet)
        _, second = train(cfg, shape, opt, mesh=mesh, steps=10,
                          ckpt_dir=d, ckpt_every=5, log=quiet)
        resumed = first[:5] + second
        assert np.allclose(full[5:], second, atol=1e-5), (full, second)
        print("RESUME OK")
    """)
    assert "RESUME OK" in out
