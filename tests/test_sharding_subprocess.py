"""Sharded-execution tests in a subprocess with 8 host devices.

(The main test process must keep the default single device — see conftest.)
These actually EXECUTE sharded programs, unlike the dry-run which only
compiles them.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


@pytest.mark.slow
def test_sharded_train_step_runs():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config, TrainConfig
        from repro.configs.base import ShapeConfig
        from repro.distributed.context import DistContext
        from repro.distributed.steps import build_train_step
        from repro.models import LM
        from repro.optim import init_opt_state

        cfg = get_config('yi-6b', reduced=True)
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ctx = DistContext.create(cfg, mesh)
        shape = ShapeConfig('t', 'train', 32, 4)
        lm = LM(cfg, max_seq=33)
        tc = TrainConfig(microbatches=2, remat='full')
        with mesh:
            jf, (ap, ao, ab) = build_train_step(lm, tc, ctx, shape)
            params = lm.init(jax.random.PRNGKey(0))
            opt = init_opt_state(params)
            batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1),
                                                  (4, 33), 0, cfg.vocab_size)}
            p2, o2, m = jf(params, opt, batch)
            print('LOSS', float(m['loss']), int(o2.step))
    """)
    loss = float(out.split("LOSS ")[1].split()[0])
    assert 0.0 < loss < 20.0


@pytest.mark.slow
def test_sp_decode_attention_matches_plain():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed.context import DistContext
        from repro.distributed.decode_attn import sp_decode_attention
        from repro.models.attention import cache_write_plain, decode_attention_plain

        cfg = get_config('yi-6b', reduced=True)
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ctx = DistContext.create(cfg, mesh)
        B, KV, S, hd, H = 4, 2, 64, 16, 4
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
        kc = jax.random.normal(ks[1], (B, KV, S, hd), jnp.float32)
        vc = jax.random.normal(ks[2], (B, KV, S, hd), jnp.float32)
        nk = jax.random.normal(ks[3], (B, 1, KV, hd), jnp.float32)
        nv = jax.random.normal(ks[4], (B, 1, KV, hd), jnp.float32)
        pos = jnp.array([5, 17, 33, 63])

        with mesh:
            o_sp, k_sp, v_sp = jax.jit(
                lambda *a: sp_decode_attention(ctx, *a))(q, kc, vc, nk, nv, pos)
        k_pl, v_pl = cache_write_plain(kc, vc, nk, nv, pos)
        o_pl = decode_attention_plain(q, k_pl, v_pl, pos)
        np.testing.assert_allclose(np.asarray(o_sp), np.asarray(o_pl),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(k_sp), np.asarray(k_pl), atol=0)
        print('SP_MATCH')
    """)
    assert "SP_MATCH" in out


@pytest.mark.slow
def test_shardmap_moe_matches_dense_oracle():
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed.context import DistContext
        from repro.models.moe import moe_ffn, moe_spec
        from repro.models.layers import init_params

        cfg = dataclasses.replace(get_config('qwen3-moe-235b-a22b', reduced=True),
                                  capacity_factor=16.0)  # no drops => exact
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ctx = DistContext.create(cfg, mesh)
        ctx.extra['moe_impl'] = 'shardmap'
        p = init_params(moe_spec(cfg), jax.random.PRNGKey(0), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                              jnp.float32)
        y_ref, _ = moe_ffn(p, x, cfg, None)
        with mesh:
            y_sm, _ = jax.jit(lambda p, x: moe_ffn(p, x, cfg, ctx))(p, x)
            g = jax.jit(jax.grad(lambda p, x: jnp.sum(
                moe_ffn(p, x, cfg, ctx)[0] ** 2)))(p, x)
        np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)
        assert bool(jnp.all(jnp.isfinite(g['w_gate'])))
        print('SHARDMAP_MOE_OK')
    """)
    assert "SHARDMAP_MOE_OK" in out


@pytest.mark.slow
def test_multipod_mesh_dev_scale():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.distributed.context import DistContext
        from repro.distributed.steps import build_prefill_step, build_decode_step
        from repro.models import LM

        cfg = get_config('recurrentgemma-9b', reduced=True)
        mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        ctx = DistContext.create(cfg, mesh)
        lm = LM(cfg, max_seq=64)
        shape = ShapeConfig('p', 'prefill', 64, 4)
        with mesh:
            jf, args = build_prefill_step(lm, ctx, shape)
            jf.lower(*args).compile()
        shape_d = ShapeConfig('d', 'decode', 64, 8)
        with mesh:
            jd, argsd = build_decode_step(lm, ctx, shape_d)
            jd.lower(*argsd).compile()
        print('MULTIPOD_OK')
    """)
    assert "MULTIPOD_OK" in out
