"""Distributed-semantics tests on 8 virtual CPU devices (subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8):

  * sharded (DP×TP) train step == single-device train step
  * error-feedback int8 compressed cross-"pod" psum inside shard_map
  * elastic checkpoint restore across different mesh shapes
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env, timeout=420,
                         cwd=REPO)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.models.common import ModelConfig
    from repro.optim.adamw import OptConfig
    from repro.train.step import (build_train_step, make_train_state,
                                  train_state_shardings)
    from repro.data.tokens import TokenDataConfig, synth_token_batch

    assert len(jax.devices()) == 8
    cfg = ModelConfig(family="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype=jnp.float32, remat=False)
    opt = OptConfig(lr=1e-2, warmup_steps=0, total_steps=10, weight_decay=0.0)
    data = TokenDataConfig(vocab_size=256, seq_len=32, global_batch=8, seed=3)
    batch = synth_token_batch(data, 0)

    # single device
    s0 = make_train_state(jax.random.PRNGKey(0), cfg)
    step = jax.jit(build_train_step(cfg, opt))
    s0, m0 = step(s0, batch)

    # 4-way data x 2-way tensor mesh
    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    s1 = make_train_state(jax.random.PRNGKey(0), cfg)
    with mesh:
        specs = train_state_shardings(cfg, mesh, jax.eval_shape(lambda: s1))
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
        bsh = {"tokens": NamedSharding(mesh, P("data", None))}
        stepd = jax.jit(build_train_step(cfg, opt),
                        in_shardings=(sh, bsh), out_shardings=(sh, None))
        s1, m1 = stepd(s1, batch)
    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(s0.params), jax.tree.leaves(s1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    print("SHARDED==SINGLE OK")
    """)


def test_compressed_psum_shard_map():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.optim.compress import compressed_psum

    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("pod", "data"))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))   # per-rank rows

    def f(g_local, res):
        out, new_res = compressed_psum(g_local, "pod", bits=8, residual=res)
        return out, new_res

    fm = jax.shard_map(f, mesh=mesh,
                   in_specs=(P(("pod", "data")), P(("pod", "data"))),
                   out_specs=(P(("pod", "data")), P(("pod", "data"))))
    res = jnp.zeros_like(g)
    out, res = fm(g, res)
    # exact mean over the pod axis of the uncompressed input, within int8 tol
    g2 = g.reshape(2, 4, 1, 64)
    want = jnp.broadcast_to(g2.mean(0, keepdims=True), g2.shape).reshape(8, 1, 64)
    scale = jnp.abs(g).max() / 127.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(want)[:, 0],
                               atol=float(scale) * 1.1)
    # error feedback: residual shrinks the NEXT round's error
    out2, res2 = fm(g, res)
    e1 = np.abs(np.asarray(out) - np.asarray(want)[:, 0]).mean()
    e2 = np.abs(np.asarray((out + out2) / 2) - np.asarray(want)[:, 0]).mean()
    assert e2 <= e1 + 1e-7, (e1, e2)
    print("COMPRESSED PSUM OK")
    """)


def test_elastic_checkpoint_reshard():
    _run("""
    import tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.checkpoint.store import (save_checkpoint, load_checkpoint,
                                        restore_into, reshard)
    from repro.models.common import ModelConfig
    from repro.train.step import make_train_state, train_state_shardings

    cfg = ModelConfig(family="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype=jnp.float32, remat=False)
    state = make_train_state(jax.random.PRNGKey(0), cfg)

    # save from an 8-device (4x2) mesh
    mesh_a = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    specs_a = train_state_shardings(cfg, mesh_a, jax.eval_shape(lambda: state))
    sh_a = jax.tree.map(lambda s: NamedSharding(mesh_a, s), specs_a,
                        is_leaf=lambda x: isinstance(x, P))
    placed = reshard(state, sh_a)
    d = tempfile.mkdtemp()
    save_checkpoint(d, 3, placed)

    # restore onto a DIFFERENT mesh (2x4) — elastic scaling
    mesh_b = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
    specs_b = train_state_shardings(cfg, mesh_b, jax.eval_shape(lambda: state))
    sh_b = jax.tree.map(lambda s: NamedSharding(mesh_b, s), specs_b,
                        is_leaf=lambda x: isinstance(x, P))
    step, loaded = load_checkpoint(d)
    restored = restore_into(state, loaded)
    placed_b = reshard(restored, sh_b)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(placed_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("ELASTIC RESHARD OK")
    """)
