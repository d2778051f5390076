"""Distribution tests — run in a subprocess with 8 fake host devices so
the main test process keeps a single device (per the dry-run contract)."""
import os
import subprocess
import sys
import textwrap


SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_moe_ep_matches_local_with_grads():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        import repro.models.moe as moe
        from repro.launch.mesh import make_debug_mesh
        moe._TOKEN_CHUNK = 8     # force the chunked path
        from repro.configs import get_config
        mesh = make_debug_mesh()      # 2 data x 4 model
        for arch in ("granite-moe-3b-a800m", "deepseek-v3-671b"):
            cfg = get_config(arch).reduced()
            cfg = cfg.replace(moe=dataclasses.replace(
                cfg.moe, capacity_factor=8.0))
            p = moe.init_moe(jax.random.PRNGKey(1), cfg, jnp.float32)
            x = jnp.asarray(np.random.default_rng(0).normal(
                size=(4, 8, cfg.d_model)) * 0.1, jnp.float32)
            y_loc, aux_l = moe.moe_local(p, cfg, x)
            y_ep, aux_e = jax.jit(lambda x: moe.moe_ep(
                p, cfg, x, mesh, dp_axes=("data",)))(x)
            err = float(np.max(np.abs(np.asarray(y_ep) - np.asarray(y_loc))))
            assert err < 1e-5, (arch, err)
            # chunked EP computes the load-balance aux per token-chunk
            # (standard per-microbatch approximation) - close, not equal
            assert abs(float(aux_l) - float(aux_e)) < 2e-2
            g = jax.grad(lambda xx: moe.moe_ep(
                p, cfg, xx, mesh, dp_axes=("data",))[0].sum())(x)
            assert np.isfinite(np.asarray(g)).all()
    """)


def test_sharded_train_step_matches_single_device():
    """pjit'd train step on a 2x4 mesh == single-device step numerically."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import Model
        from repro.training import adamw, make_train_step
        from repro.launch import shardings as sh
        from repro.launch.mesh import make_debug_mesh

        cfg = get_config("llama3.2-1b").reduced()
        mesh = make_debug_mesh()      # 2 data x 4 model
        key = jax.random.PRNGKey(0)
        batch = {"tokens": jnp.ones((4, 16), jnp.int32),
                 "targets": jnp.ones((4, 16), jnp.int32)}

        m1 = Model(cfg)
        p1 = m1.init(key)
        o1 = adamw(lr=1e-2); s1 = o1.init(p1)
        step1 = jax.jit(make_train_step(m1, o1))
        np1, _, met1 = step1(p1, s1, batch)

        m2 = Model(cfg, mesh=mesh, remat=True)
        p2 = m2.init(key)
        ps = sh.params_shardings(m2, mesh, zero3=True)
        p2 = jax.device_put(p2, ps)
        o2 = adamw(lr=1e-2); s2 = o2.init(p2)
        step2 = jax.jit(make_train_step(m2, o2))
        np2, _, met2 = step2(p2, s2, batch)

        assert abs(float(met1["loss"]) - float(met2["loss"])) < 1e-4
        for a, b in zip(jax.tree.leaves(np1), jax.tree.leaves(np2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3)
    """)


def test_sharded_prefill_decode_matches():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import Model
        from repro.launch import shardings as sh
        from repro.launch.mesh import make_debug_mesh

        cfg = get_config("qwen3-4b").reduced()
        mesh = make_debug_mesh()      # 2 data x 4 model
        key = jax.random.PRNGKey(0)
        toks = jnp.asarray(np.random.default_rng(0).integers(
            3, cfg.vocab, (4, 16)), jnp.int32)

        m1 = Model(cfg)
        p1 = m1.init(key)
        c1 = m1.init_cache(4, 20)
        l1, c1 = m1.prefill(p1, {"tokens": toks}, c1)
        d1, _ = m1.decode_step(p1, c1, toks[:, :1], 16)

        m2 = Model(cfg, mesh=mesh)
        p2 = jax.device_put(m2.init(key),
                            sh.params_shardings(m2, mesh))
        c2 = m2.init_cache(4, 20)
        l2, c2 = jax.jit(m2.prefill, static_argnames=("resume",))(
            p2, {"tokens": toks}, c2, 0, None)
        d2, _ = jax.jit(m2.decode_step)(p2, c2, toks[:, :1], 16)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   atol=5e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   atol=5e-4, rtol=1e-3)
    """)


def test_mini_dryrun_lowers_on_8_devices():
    """build_step lowers+compiles for a reduced arch on a small mesh —
    the same machinery the 512-device production dry-run uses."""
    run_sub("""
        import jax
        import dataclasses
        from repro.config import ShapeConfig
        from repro.configs import get_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.specs import build_step
        mesh = make_debug_mesh()      # 2 data x 4 model
        shape_t = ShapeConfig("t", 64, 8, "train")
        shape_d = ShapeConfig("d", 64, 8, "decode", force_window=32)
        for arch in ("llama3.2-1b", "granite-moe-3b-a800m", "mamba2-780m",
                     "whisper-base", "qwen2-vl-2b"):
            cfg = get_config(arch).reduced()
            for shape in (shape_t, shape_d):
                jitted, args, _ = build_step(cfg, shape, mesh, donate=False)
                c = jitted.lower(*args).compile()
                assert c.cost_analysis() is not None
    """)
