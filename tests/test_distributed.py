"""Multi-device integration tests (subprocess with forced host devices so
the main test process keeps a single device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run_in_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    code = textwrap.dedent("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config, smoke_config
        from repro.dist import sharding as shd
        from repro.models import model as M
        from repro.train import optimizer as opt_lib
        from repro.train.train_step import make_train_step

        cfg = smoke_config(get_config("internlm2_1_8b"))
        params = M.init(jax.random.PRNGKey(0), cfg)
        opt = opt_lib.init(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        ts = make_train_step(cfg, remat=False)

        # single device
        p1, o1, m1 = jax.jit(ts)(params, opt, batch)

        # 4x2 mesh
        mesh = shd.make_mesh((4, 2), ("data", "model"))
        shd.set_mesh(mesh)
        in_sh = (shd.param_shardings(params),
                 type(opt)(None, shd.param_shardings(opt.master),
                           shd.param_shardings(opt.m),
                           shd.param_shardings(opt.v)),
                 shd.batch_shardings(batch))
        with mesh:
            p2, o2, m2 = jax.jit(ts, in_shardings=in_sh)(params, opt,
                                                         batch)
        print(json.dumps({"l1": float(m1["loss"]),
                          "l2": float(m2["loss"])}))
    """)
    res = _run_in_subprocess(code)
    assert abs(res["l1"] - res["l2"]) < 0.05, res


@pytest.mark.slow
def test_moe_ep_matches_scatter_path():
    code = textwrap.dedent("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.dist import sharding as shd
        from repro.models.common import ModelConfig
        from repro.models.moe import init_moe, moe_block, moe_block_scatter

        cfg = ModelConfig(name="t", family="moe_gqa", n_layers=1,
                          d_model=16, n_heads=4, d_ff=32, vocab=8,
                          n_experts=8, top_k=2, d_ff_expert=32,
                          capacity_factor=8.0, dtype="float32")
        p = init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16))

        ref, _ = moe_block_scatter(p, x, cfg)

        mesh = shd.make_mesh((2, 4), ("data", "model"))
        shd.set_mesh(mesh)
        with mesh:
            out, _ = jax.jit(lambda p, x: moe_block(p, x, cfg))(p, x)
        err = float(jnp.max(jnp.abs(out - ref)))
        print(json.dumps({"err": err}))
    """)
    res = _run_in_subprocess(code)
    assert res["err"] < 1e-3, res


@pytest.mark.slow
def test_elastic_restore_onto_different_mesh(tmp_path):
    code = textwrap.dedent("""
        import json, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.dist import sharding as shd
        from repro.train import checkpoint as ckpt

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        mesh1 = shd.make_mesh((8,), ("data",))
        sh1 = {"w": NamedSharding(mesh1, P("data", None))}
        placed = jax.device_put(tree, sh1)
        ckpt.save("%s", 1, placed)

        # restore onto a *different* topology (2x2 submesh, model axis)
        mesh2 = shd.make_mesh((2, 2), ("data", "model"))
        sh2 = {"w": NamedSharding(mesh2, P("model", "data"))}
        restored, step = ckpt.restore("%s", jax.eval_shape(lambda: tree),
                                      shardings=sh2)
        ok = bool(np.array_equal(np.asarray(restored["w"]),
                                 np.asarray(tree["w"])))
        n_shards = len(restored["w"].addressable_shards)
        print(json.dumps({"ok": ok, "n_shards": n_shards}))
    """ % (tmp_path, tmp_path))
    res = _run_in_subprocess(code)
    assert res["ok"] and res["n_shards"] == 4, res
