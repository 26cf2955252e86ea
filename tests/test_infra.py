"""Substrate tests: optimizer, data pipeline, checkpointing, fault-tolerant
trainer (preemption/resume/straggler), all on the local device."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import CheckpointManager
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.optim import adamw
from repro.runtime.trainer import StragglerDetector, Trainer, TrainLoopConfig


# ------------------------------------------------------------------ optimizer
def test_adamw_converges_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=0, total_steps=200,
                            weight_decay=0.0, clip_norm=10.0)
    params = {"w": jnp.array([3.0, -2.0])}
    state = adamw.init(params)
    target = jnp.array([1.0, 1.0])

    @jax.jit
    def step(params, state):
        grads = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
        return adamw.update(cfg, grads, state, params)

    for _ in range(200):
        params, state, stats = step(params, state)
    np.testing.assert_allclose(np.asarray(params["w"]), [1.0, 1.0], atol=1e-2)


def test_adamw_clips_gradients():
    cfg = adamw.AdamWConfig(clip_norm=1.0, warmup_steps=0)
    params = {"w": jnp.zeros(3)}
    state = adamw.init(params)
    grads = {"w": jnp.array([100.0, 0.0, 0.0])}
    _, _, stats = adamw.update(cfg, grads, state, params)
    assert float(stats["grad_norm"]) == pytest.approx(100.0)


def test_schedule_warmup_and_cosine():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110,
                            min_lr_ratio=0.1)
    assert float(adamw.schedule(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(adamw.schedule(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(adamw.schedule(cfg, jnp.int32(110))) == pytest.approx(0.1)


def test_bf16_params_fp32_master():
    cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=0)
    params = {"w": jnp.ones(4, jnp.bfloat16)}
    state = adamw.init(params)
    assert state["master"]["w"].dtype == jnp.float32
    grads = {"w": jnp.full(4, 0.001, jnp.bfloat16)}
    new_params, state, _ = adamw.update(cfg, grads, state, params)
    assert new_params["w"].dtype == jnp.bfloat16


# ----------------------------------------------------------------------- data
def test_data_deterministic_and_resumable():
    cfg = DataConfig(vocab=1000, seq_len=32, global_batch=8)
    a = SyntheticLM(cfg).batch(7)
    b = SyntheticLM(cfg).batch(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = SyntheticLM(cfg).batch(8)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_data_host_sharding_disjoint():
    cfg = DataConfig(vocab=1000, seq_len=16, global_batch=8)
    h0 = SyntheticLM(cfg, host_index=0, n_hosts=2).batch(3)
    h1 = SyntheticLM(cfg, host_index=1, n_hosts=2).batch(3)
    assert h0["tokens"].shape == (4, 16)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_data_labels_shifted():
    cfg = DataConfig(vocab=100, seq_len=16, global_batch=2)
    b = SyntheticLM(cfg).batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# ----------------------------------------------------------------- checkpoint
def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (4, 8)),
            "b": {"c": jnp.arange(6, dtype=jnp.int32),
                  "d": [jnp.ones(3), jnp.zeros(2)]}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    tree = _tree()
    mgr.save(10, tree, blocking=True)
    like = jax.eval_shape(lambda: tree)
    restored = mgr.restore(10, like)
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                            np.asarray(y)),
                 tree, restored)


def test_checkpoint_atomicity_no_commit(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, _tree(), blocking=True)
    os.remove(os.path.join(mgr._step_dir(5), "COMMIT"))
    assert mgr.latest_step() is None


def test_checkpoint_checksum_detects_corruption(tmp_path):
    import json
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, _tree(), blocking=True)
    manifest_path = os.path.join(mgr._step_dir(3), "MANIFEST.json")
    manifest = json.load(open(manifest_path))
    manifest["leaves"]["a"]["crc32"] ^= 0xFF   # bit-rot on the recorded crc
    json.dump(manifest, open(manifest_path, "w"))
    with pytest.raises(IOError):
        mgr.restore(3, jax.eval_shape(lambda: _tree()))


def test_checkpoint_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(), blocking=True)
    assert mgr.valid_steps() == [3, 4]


def test_checkpoint_async_overlap(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree())       # non-blocking
    mgr.save(2, _tree())       # waits for 1, then writes 2
    mgr.wait()
    assert 2 in mgr.valid_steps()


# -------------------------------------------------------------------- trainer
def _tiny_trainer(tmp_path, total=60, ckpt_every=10):
    opt_cfg = adamw.AdamWConfig(lr=0.15, warmup_steps=0, total_steps=total,
                                weight_decay=0.0)
    params = {"w": jnp.array([4.0])}
    opt_state = adamw.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        def loss_fn(p):
            return jnp.sum((p["w"] - batch["target"]) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        p, s, stats = adamw.update(opt_cfg, grads, opt_state, params)
        return p, s, {"loss": loss, **stats}

    def batch_fn(i):
        return {"target": jnp.array([1.0])}

    return Trainer(TrainLoopConfig(total_steps=total, ckpt_every=ckpt_every,
                                   ckpt_dir=str(tmp_path), log_every=1000),
                   step, params, opt_state, batch_fn)


def test_trainer_runs_and_checkpoints(tmp_path):
    tr = _tiny_trainer(tmp_path)
    out = tr.run()
    assert out["final_step"] == 60
    assert tr.ckpt.latest_step() == 60
    assert float(tr.params["w"][0]) == pytest.approx(1.0, abs=0.2)


def test_trainer_preemption_and_resume(tmp_path):
    tr = _tiny_trainer(tmp_path, total=1000, ckpt_every=5)
    orig_observe = tr.straggler.observe
    count = {"n": 0}

    def preempt_after(step, dt):
        count["n"] += 1
        if count["n"] >= 12:
            tr._preempted = True      # simulated SIGTERM
        return orig_observe(step, dt)

    tr.straggler.observe = preempt_after
    out = tr.run()
    assert out["preempted"]
    stopped_at = out["final_step"]
    assert tr.ckpt.latest_step() == stopped_at

    tr2 = _tiny_trainer(tmp_path, total=stopped_at + 10, ckpt_every=5)
    resumed = tr2.maybe_restore()
    assert resumed == stopped_at
    out2 = tr2.run()
    assert out2["final_step"] == stopped_at + 10


def test_straggler_detector():
    det = StragglerDetector(k=3.0, alpha=0.5)
    for i in range(10):
        assert not det.observe(i, 0.1)
    assert det.observe(10, 1.0)       # 10x slower -> flagged
    assert det.report()["n_flagged"] == 1
    assert not det.observe(11, 0.1)   # ewma not polluted by the outlier


# ------------------------------------------------------- compile cache place
@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and the code sets no other directory;
    otherwise the cache goes to the caller's fixed directory."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.device import use_compile_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = use_compile_cache(tmp_path / "repo" / ".jax_cache")
        if from_env:
            assert got == str(tmp_path / "env")
            assert jax.config.jax_compilation_cache_dir == saved[keys[0]]
        else:
            assert got == str(tmp_path / "repo" / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
