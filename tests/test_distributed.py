"""Distributed-behaviour tests on a fake multi-device mesh.

Each test runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count so jax sees 8 CPU 'devices' (the main pytest process must keep
its single-device view for the other tests)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    # CPU devices only: the parent may already hold the TPU, and a chip
    # belongs to one process.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    script = textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


def test_train_step_executes_on_mesh():
    """Real (not just compiled) sharded train step: finite loss, params move,
    and the loss matches the single-device value (SPMD == math)."""
    out = run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.configs import get_smoke
        from repro.launch.mesh import make_test_mesh
        from repro.models import steps as ST
        from repro.models.transformer import init_lm
        from repro.optim import adamw
        from repro.sharding import rules
        from repro.sharding.api import make_parallel
        import dataclasses

        cfg = dataclasses.replace(get_smoke("qwen2-moe-a2.7b"), dtype="float32")
        params = init_lm(jax.random.PRNGKey(0), cfg)
        opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
        opt = adamw.init(params)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 64), 0, cfg.vocab)}

        # single-device reference
        ref_step = jax.jit(ST.make_train_step(cfg, opt_cfg, None))
        _, _, ref_metrics = ref_step(params, opt, batch)

        mesh = make_test_mesh(2, 4)
        par = make_parallel(mesh)
        p_sh = rules.params_shardings(mesh, jax.eval_shape(lambda: params))
        o_sh = rules.opt_state_shardings(mesh, jax.eval_shape(lambda: opt))
        b_sh = rules.batch_shardings(mesh, jax.eval_shape(lambda: batch))
        params_d = jax.device_put(params, p_sh)
        opt_d = jax.device_put(opt, o_sh)
        batch_d = jax.device_put(batch, b_sh)
        step = jax.jit(ST.make_train_step(cfg, opt_cfg, par),
                       in_shardings=(p_sh, o_sh, b_sh))
        with mesh:
            p2, o2, metrics = step(params_d, opt_d, batch_d)
        l_sharded = float(metrics["loss"])
        l_ref = float(ref_metrics["loss"])
        assert np.isfinite(l_sharded)
        assert abs(l_sharded - l_ref) < 5e-3 * max(1.0, abs(l_ref)), (l_sharded, l_ref)
        print("OK", l_sharded, l_ref)
    """)
    assert "OK" in out


def test_moe_active_vs_passive_same_math_different_collectives():
    out = run_sub("""
        import dataclasses, re
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.configs import get_smoke
        from repro.launch.mesh import make_test_mesh
        from repro.models import moe as M
        from repro.sharding.api import Parallel

        cfg = dataclasses.replace(get_smoke("qwen2-moe-a2.7b"), dtype="float32")
        p = M.moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
        mesh = make_test_mesh(2, 4)
        outs, texts = [], []
        for strat in ("active", "passive"):
            par = Parallel(mesh=mesh, dp_axes=("data",), psum_strategy=strat)
            f = jax.jit(lambda pp, xx: M.moe_apply(pp, xx, cfg, par)[0])
            with mesh:
                comp = f.lower(p, x).compile()
                outs.append(np.asarray(f(p, x)))
            texts.append(comp.as_text())
        np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-4)
        def bytes_of(kind, txt):
            n = 0
            for m_ in re.finditer(r'f32\\[([\\d,]+)\\]\\S*\\s+' + kind, txt):
                sz = 1
                for d in m_.group(1).split(','): sz *= int(d)
                n += sz * 4
            return n
        ag_passive = bytes_of('all-gather', texts[1])
        ag_active = bytes_of('all-gather', texts[0])
        assert ag_passive > ag_active, (ag_passive, ag_active)
        print("OK", ag_active, ag_passive)
    """)
    assert "OK" in out


def test_elastic_restart_smaller_mesh():
    """Checkpoint on a (2,4) mesh; resume on (1,4): losses keep decreasing."""
    out = run_sub("""
        import dataclasses, tempfile
        import jax, numpy as np
        from repro.configs import get_smoke
        from repro.checkpoint.store import CheckpointManager
        from repro.launch.mesh import make_test_mesh
        from repro.models import steps as ST
        from repro.models.transformer import init_lm
        from repro.optim import adamw
        from repro.runtime.elastic import largest_healthy_mesh, resume_on_mesh
        from repro.sharding import rules
        from repro.sharding.api import make_parallel

        cfg = dataclasses.replace(get_smoke("qwen2-1.5b"), dtype="float32")
        params = init_lm(jax.random.PRNGKey(0), cfg)
        opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=0, total_steps=100,
                                    weight_decay=0.0)
        opt = adamw.init(params)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfg.vocab)}

        tmp = tempfile.mkdtemp()
        ckpt = CheckpointManager(tmp)
        mesh1 = make_test_mesh(2, 4)
        par1 = make_parallel(mesh1)
        p_sh = rules.params_shardings(mesh1, jax.eval_shape(lambda: params))
        o_sh = rules.opt_state_shardings(mesh1, jax.eval_shape(lambda: opt))
        step1 = jax.jit(ST.make_train_step(cfg, opt_cfg, par1),
                        in_shardings=(p_sh, o_sh, None))
        losses = []
        with mesh1:
            p_d, o_d = jax.device_put(params, p_sh), jax.device_put(opt, o_sh)
            for i in range(4):
                p_d, o_d, m = step1(p_d, o_d, batch)
                losses.append(float(m["loss"]))
        ckpt.save(4, {"params": p_d, "opt_state": o_d}, blocking=True)

        # "lose" half the devices -> (1, 4) mesh
        mesh2 = largest_healthy_mesh(4, model_parallel=4)
        step_r, p_r, o_r = resume_on_mesh(
            ckpt, mesh2, jax.eval_shape(lambda: params),
            jax.eval_shape(lambda: opt))
        par2 = make_parallel(mesh2)
        step2 = jax.jit(ST.make_train_step(cfg, opt_cfg, par2))
        with mesh2:
            for i in range(4):
                p_r, o_r, m = step2(p_r, o_r, batch)
                losses.append(float(m["loss"]))
        assert step_r == 4
        assert losses[-1] < losses[0], losses
        deltas = np.diff(losses)
        assert (deltas < 0.05).all(), losses   # no loss spike at the re-shard
        print("OK", [round(l, 3) for l in losses])
    """)
    assert "OK" in out


def test_pipeline_parallel_prototype():
    out = run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import AxisType, Mesh
        from repro.runtime.pipeline import pipeline_apply

        devs = np.array(jax.devices()[:2]).reshape(2,)
        mesh = Mesh(devs, ("pod",), axis_types=(AxisType.Auto,))
        # 2-stage pipeline of affine maps
        w = jnp.stack([jnp.eye(4) * 2.0, jnp.eye(4) * 3.0])  # stage weights
        def stage_fn(wi, x):
            return x @ wi
        xs = jnp.arange(4 * 8 * 4, dtype=jnp.float32).reshape(4, 8, 4)
        out = pipeline_apply(mesh, 2, stage_fn, w, xs)
        want = xs * 6.0
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_int8_error_feedback_allreduce():
    out = run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.launch.mesh import make_test_mesh
        from repro.optim.compress import compressed_allreduce, init_error_feedback

        mesh = make_test_mesh(8, 1)
        grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 64))}
        err = init_error_feedback(grads)
        with mesh:
            mean1, err = compressed_allreduce(grads, err, mesh, ("data",))
        # every device contributed the same grad -> mean == grad (to int8 tol)
        rel = np.abs(np.asarray(mean1["w"]) - np.asarray(grads["w"])).max()
        assert rel < 0.05, rel
        # error feedback: residual carried
        resid = np.abs(np.asarray(err["w"])).max()
        print("OK", rel, resid)
    """)
    assert "OK" in out


def test_flash_decode_matches_baseline():
    """shard_map flash-decoding == plain decode (hillclimb 2 correctness)."""
    out = run_sub("""
        import dataclasses, jax, numpy as np
        from repro.configs import get_smoke
        from repro.launch.mesh import make_test_mesh
        from repro.models import steps as ST
        from repro.models.transformer import init_lm
        from repro.sharding.api import make_parallel

        cfg = dataclasses.replace(get_smoke("granite-8b"), dtype="float32")
        params = init_lm(jax.random.PRNGKey(0), cfg)
        mesh = make_test_mesh(2, 4)
        B, S = 8, 64
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
        outs = {}
        for fd in (False, True):
            par = make_parallel(mesh, flash_decode=fd)
            prefill = jax.jit(ST.make_prefill_step(cfg, S, par))
            decode = jax.jit(ST.make_decode_step(cfg, par))
            with mesh:
                logits, caches = prefill(params, {"tokens": toks[:, :S-3]})
                seq = []
                for i in range(3):
                    logits, caches = decode(params, caches,
                                            toks[:, S-3+i:S-2+i])
                    seq.append(np.asarray(logits))
            outs[fd] = np.stack(seq)
        err = np.abs(outs[False] - outs[True]).max()
        assert err < 2e-4, err
        print("OK", err)
    """)
    assert "OK" in out
