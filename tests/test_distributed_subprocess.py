"""Multi-device behaviours that need >1 device: run in a subprocess with
xla_force_host_platform_device_count (must be set before jax init, hence the
separate process)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(script: str, devices: int = 8, timeout: int = 540):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharding_rules_on_8dev_mesh():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.distributed.sharding import rules_for, param_specs, batch_specs, cache_specs
        from repro.configs import ARCHS
        from repro.models import lm

        mesh = make_mesh((2, 4), ("data", "model"))
        arch = ARCHS["qwen3-8b"]
        cfg = arch.smoke
        params = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.key(0))
        rules = rules_for(cfg, mesh, "train")
        specs = param_specs(params, cfg, mesh, rules)
        # attention q: (L, D, H, hd): heads sharded over model (4 heads / 4)
        qspec = specs["layers"]["attn"]["q"]
        assert qspec[2] == "model", qspec
        bspecs = batch_specs({"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}, mesh, rules)
        assert bspecs["tokens"][0] == "data", bspecs
        cache = jax.eval_shape(lambda: lm.init_cache(cfg, 8, 32))
        cspecs = cache_specs(cache, cfg, mesh, rules)
        print("OK", qspec, bspecs["tokens"], cspecs.k)
    """)
    assert "OK" in out


def test_compressed_psum_matches_plain():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.distributed.compression import compressed_psum

        from jax import shard_map

        mesh = make_mesh((8,), ("data",))
        x = jax.random.normal(jax.random.key(0), (8, 128), jnp.float32)

        @jax.jit
        def plain(x):
            return shard_map(
                lambda xs: jax.lax.psum(xs[0], "data")[None],
                mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)

        @jax.jit
        def comp(x):
            return shard_map(
                lambda xs: compressed_psum(xs[0], "data")[None],
                mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)

        p = np.asarray(plain(x))
        c = np.asarray(comp(x))
        scale = np.abs(p).max()
        err = np.abs(p - c).max() / scale
        assert err < 0.05, f"relative err {err}"
        print("OK compressed_psum rel err", err)
    """)
    assert "OK" in out


def test_small_mesh_dryrun_train_and_decode():
    """End-to-end mini dry-run: smoke configs, (2,4) mesh, train + decode."""
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.launch.hlo_costs import analyze_hlo
        from repro.distributed.sharding import (rules_for, param_specs,
            opt_state_specs, batch_specs, cache_specs, tree_shardings)
        from repro.configs import ARCHS, input_specs, decode_operand_specs
        from repro.models.config import ShapeSpec
        from repro.models import lm
        from repro.train.optimizer import make_optimizer, warmup_cosine
        from repro.train.train_step import TrainState, make_train_step, make_serve_step

        mesh = make_mesh((2, 4), ("data", "model"))
        shape = ShapeSpec("mini_train", 64, 8, "train")
        for arch_id in ("qwen3-8b", "qwen2-moe-a2.7b", "mamba2-130m"):
            cfg = dataclasses.replace(ARCHS[arch_id].smoke, remat=True)
            opt = make_optimizer("adamw", warmup_cosine(1e-3))
            state = jax.eval_shape(
                lambda k: TrainState(jnp.zeros((), jnp.int32),
                                     lm.init_params(k, cfg),
                                     opt.init(lm.init_params(k, cfg))),
                jax.random.key(0))
            rules = rules_for(cfg, mesh, "train")
            pspecs = param_specs(state.params, cfg, mesh, rules)
            ospecs = opt_state_specs(state.opt_state, pspecs, state.params, mesh)
            sspecs = TrainState(P(), pspecs, ospecs)
            batch = input_specs(cfg, shape)
            bspecs = batch_specs(batch, mesh, rules)
            step = make_train_step(cfg, opt, accum_steps=2)
            with mesh:
                lowered = jax.jit(step,
                    in_shardings=(tree_shardings(sspecs, mesh), tree_shardings(bspecs, mesh)),
                    out_shardings=(tree_shardings(sspecs, mesh), None)
                ).lower(state, batch)
                compiled = lowered.compile()
            cost = analyze_hlo(compiled.as_text())
            assert cost.flops > 0 and cost.bytes > 0
            print("OK train", arch_id, f"flops={cost.flops:.2e}")

        # decode cell for the dense smoke config
        cfg = ARCHS["qwen3-8b"].smoke
        dshape = ShapeSpec("mini_decode", 64, 8, "decode")
        cache, token, pos, pos_ref = decode_operand_specs(cfg, dshape)
        params = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.key(0))
        params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
                              if jnp.issubdtype(s.dtype, jnp.floating) else s, params)
        rules = rules_for(cfg, mesh, "decode")
        pspecs = param_specs(params, cfg, mesh, rules)
        cspecs = cache_specs(cache, cfg, mesh, rules)
        step = make_serve_step(cfg, "decode")
        with mesh:
            compiled = jax.jit(step, in_shardings=(
                tree_shardings(pspecs, mesh), tree_shardings(cspecs, mesh),
                NamedSharding(mesh, P("data")), NamedSharding(mesh, P()))
            ).lower(params, cache, token, pos).compile()
        cost = analyze_hlo(compiled.as_text())
        assert cost.flops > 0
        print("OK decode qwen3-smoke")
    """)
    assert out.count("OK") == 4
