"""Distributed behavior on 8 host devices — run in subprocesses so the main
test process keeps a single CPU device (the dry-run rule).

Whole module is tier-2 (``slow``): every test compiles a multi-device
training/pipeline step in a fresh subprocess (~10–20 s each).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_pjit_train_step_on_4x2_mesh():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.distributed.sharding import tree_shardings, batch_shardings
        from repro.launch.mesh import make_mesh
        from repro.models.params import init_params
        from repro.models.transformer import model_defs
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import init_train_state, make_train_step
        from repro.train.data import DataConfig, synthetic_batch
        cfg = get_reduced('qwen1_5_0_5b')
        mesh = make_mesh((4, 2), ('data', 'model'))
        defs = model_defs(cfg)
        sh = tree_shardings(defs, mesh)
        params = jax.tree.map(jax.device_put,
                              init_params(defs, jax.random.key(0)), sh)
        state = init_train_state(params)
        step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3)))
        d = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
        losses = []
        with jax.set_mesh(mesh):
            for s in range(8):
                state, m = step(state, synthetic_batch(d, s))
                losses.append(float(m['loss']))
        assert losses[-1] < losses[0], losses
        print('OK', losses[0], losses[-1])
    """)
    assert "OK" in out


@pytest.mark.parametrize("exchange", ["packed", "psum"])
def test_compressed_majority_vote_training(exchange):
    """Both vote collectives (bit-packed all-gather and the Σ±1 psum
    control) must train; their majority semantics are identical."""
    out = run_with_devices(f"""
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.launch.mesh import make_mesh
        from repro.launch.train import setup
        from repro.train.optimizer import AdamWConfig
        from repro.train.data import DataConfig, synthetic_batch
        cfg = get_reduced('qwen1_5_0_5b')
        mesh = make_mesh((4, 2), ('data', 'model'))
        state, _, step = setup(cfg, mesh, AdamWConfig(lr=5e-3),
                               compressed=True, exchange={exchange!r})
        d = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
        batch = synthetic_batch(d, 0)    # fixed batch: optimization signal
        losses = []
        for s in range(20):
            state, m = step(state, batch)
            losses.append(float(m['loss']))
        assert losses[-1] < losses[0] - 0.05, losses
        print('OK', losses[0], losses[-1])
    """)
    assert "OK" in out


def test_pipeline_parallel_matches_sequential():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply, stack_stage_params
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ('pipe', 'model'))
        P, M, mb, d = 4, 6, 8, 16
        keys = jax.random.split(jax.random.key(0), P)
        stage_params = [ {'w': jax.random.normal(k, (d, d)) * 0.3} for k in keys ]
        def stage_fn(p, x):
            return jnp.tanh(x @ p['w'])
        x = jax.random.normal(jax.random.key(1), (M, mb, d))
        stacked = stack_stage_params(stage_params)
        y = pipeline_apply(stage_fn, stacked, x, mesh=mesh, axis='pipe')
        # sequential reference
        ref = x
        for p in stage_params:
            ref = stage_fn(p, ref)
        err = float(jnp.max(jnp.abs(y - ref)))
        assert err < 1e-5, err
        print('OK', err)
    """)
    assert "OK" in out


def test_elastic_checkpoint_restore_smaller_mesh(tmp_path):
    ck = str(tmp_path / "ck")
    run_with_devices(f"""
        import jax
        from repro.configs import get_reduced
        from repro.distributed.sharding import tree_shardings
        from repro.distributed.checkpoint import CheckpointManager
        from repro.launch.mesh import make_mesh
        from repro.models.params import init_params
        from repro.models.transformer import model_defs
        from repro.train.train_step import init_train_state
        cfg = get_reduced('qwen1_5_0_5b')
        mesh = make_mesh((4, 2), ('data', 'model'))
        defs = model_defs(cfg)
        params = jax.tree.map(jax.device_put,
                              init_params(defs, jax.random.key(0)),
                              tree_shardings(defs, mesh))
        state = init_train_state(params)
        CheckpointManager({ck!r}).save(11, state, mesh, blocking=True)
        print('SAVED')
    """, n=8)
    out = run_with_devices(f"""
        import jax, numpy as np
        from repro.configs import get_reduced
        from repro.distributed.sharding import tree_shardings
        from repro.distributed.checkpoint import CheckpointManager
        from repro.launch.mesh import make_mesh
        from repro.models.params import init_params
        from repro.models.transformer import model_defs
        from repro.train.train_step import init_train_state
        cfg = get_reduced('qwen1_5_0_5b')
        mesh = make_mesh((2, 2), ('data', 'model'))   # downscaled!
        defs = model_defs(cfg)
        like = init_train_state(init_params(defs, jax.random.key(1)))
        mgr = CheckpointManager({ck!r})
        sh = tree_shardings(defs, mesh)
        from repro.train.train_step import TrainState
        from repro.train.optimizer import AdamWState
        from repro.distributed.sharding import replicated
        st_sh = TrainState(params=sh, opt=AdamWState(
            step=replicated(mesh), m=sh, v=sh), error_fb=None)
        restored = mgr.restore(11, like, st_sh)
        ref = init_params(defs, jax.random.key(0))
        for a, b in zip(jax.tree.leaves(restored.params), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('OK restored on 2x2 from 4x2')
    """, n=4)
    assert "OK" in out


def test_two_phase_majority_vote_training():
    """H7 collective (all-to-all slice → vote → gather) trains correctly."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as PS
        from repro.configs import get_reduced
        from repro.distributed.sharding import tree_shardings
        from repro.launch.mesh import make_mesh
        from repro.models.params import init_params
        from repro.models.transformer import model_defs
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import (init_train_state,
                                            make_compressed_train_step)
        from repro.train.data import DataConfig, synthetic_batch
        cfg = get_reduced('qwen1_5_0_5b')
        mesh = make_mesh((8, 1), ('data', 'model'))
        defs = model_defs(cfg)
        params = jax.tree.map(jax.device_put,
                              init_params(defs, jax.random.key(0)),
                              tree_shardings(defs, mesh))
        state = init_train_state(params, compressed=True)
        inner, da = make_compressed_train_step(cfg, AdamWConfig(lr=5e-3),
                                               mesh, two_phase=True)
        step = jax.jit(jax.shard_map(
            inner, mesh=mesh, axis_names={'data'},
            in_specs=(jax.tree.map(lambda _: PS(), state),
                      {'tokens': PS('data'), 'labels': PS('data')}),
            out_specs=(jax.tree.map(lambda _: PS(), state),
                       {'loss': PS(), 'aux': PS(), 'grad_norm': PS(),
                        'lr': PS()}),
            check_vma=False), donate_argnums=(0,))
        d = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
        batch = synthetic_batch(d, 0)
        losses = []
        for s in range(18):
            state, m = step(state, batch)
            losses.append(float(m['loss']))
        assert losses[-1] < losses[0] - 0.03, losses
        print('OK', losses[0], losses[-1])
    """)
    assert "OK" in out


def test_pipeline_parallel_is_differentiable():
    """GPipe schedule must be trainable: jax.grad through the pipelined
    forward matches grads of the sequential composition."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import (pipeline_apply,
                                                stack_stage_params)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ('pipe', 'model'))
        P, M, mb, d = 4, 4, 4, 8
        keys = jax.random.split(jax.random.key(0), P)
        stages = [{'w': jax.random.normal(k, (d, d)) * 0.3} for k in keys]
        stacked = stack_stage_params(stages)
        x = jax.random.normal(jax.random.key(1), (M, mb, d))

        def stage_fn(p, h):
            return jnp.tanh(h @ p['w'])

        def loss_pipe(params):
            y = pipeline_apply(stage_fn, params, x, mesh=mesh, axis='pipe')
            return jnp.sum(y ** 2)

        def loss_seq(stages):
            h = x
            for p in stages:
                h = stage_fn(p, h)
            return jnp.sum(h ** 2)

        g_pipe = jax.grad(loss_pipe)(stacked)['w']
        g_seq = jnp.stack([g['w'] for g in jax.grad(loss_seq)(stages)])
        err = float(jnp.max(jnp.abs(g_pipe - g_seq)))
        assert err < 1e-4, err
        print('OK', err)
    """)
    assert "OK" in out
