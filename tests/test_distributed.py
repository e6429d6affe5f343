"""Distributed-runtime tests (8 fake host devices, subprocess-isolated
so the rest of the suite keeps seeing 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest

# each test spawns a fresh interpreter with 8 fake devices and re-jits
# from scratch; tier-1 skips them, run with -m slow.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_aggregation_strategies_numerics():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.topology import AggregationStrategy, aggregate_updates
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh()  # (4,2) data, model
        u = {"w": jnp.ones((8, 4))}
        mask = jnp.array([1., 0., 1., 1.])
        # results come back client-stacked: (4, 8, 4)
        for kind in ("cfl", "dfl_mesh", "dfl_ring"):
            s = AggregationStrategy(kind=kind, client_axes=("data",))
            out = aggregate_updates(u, mesh, s, mask)
            assert out["w"].shape == (4, 8, 4)
            np.testing.assert_allclose(np.asarray(out["w"]), 1.0, rtol=1e-5)
        # enfed neighborhoods of 2: group [0,1] only member 0 participates
        s = AggregationStrategy(kind="enfed", client_axes=("data",), neighborhood_size=2)
        out = aggregate_updates(u, mesh, s, mask)
        np.testing.assert_allclose(np.asarray(out["w"]), 1.0, rtol=1e-5)
        print("STRATEGIES-OK")
    """)
    assert "STRATEGIES-OK" in out


def test_federated_train_step_all_strategies():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import Transformer
        from repro.launch.mesh import make_debug_mesh, client_axes_for
        from repro.launch.steps import (make_federated_train_step, stack_for_clients,
                                        fed_param_shardings, num_clients)
        from repro.launch.inputs import batch_input_shardings
        from repro.core.topology import AggregationStrategy
        from repro.sharding import use_mesh
        mesh = make_debug_mesh(multi_pod=True)
        cfg = get_config("debug-moe")
        model = Transformer(cfg)
        caxes = client_axes_for(cfg, mesh)
        C = num_clients(mesh, caxes)
        losses = {}
        for kind in ("cfl", "enfed", "dfl_ring", "dfl_mesh"):
            strat = AggregationStrategy(kind=kind, client_axes=caxes, neighborhood_size=2)
            with use_mesh(mesh):
                params = model.init(jax.random.PRNGKey(0))
                step, opt = make_federated_train_step(model, mesh, strat, lr=1e-3)
                pf = stack_for_clients(params, C)
                of = stack_for_clients(opt.init(params), C)
                psh = fed_param_shardings(jax.eval_shape(lambda: pf), mesh, caxes, cfg.fsdp)
                batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
                         "labels": jnp.zeros((8, 16), jnp.int32)}
                bsh = batch_input_shardings(batch, mesh, client_stacked=True, client_axes=caxes)
                jitted = jax.jit(step, in_shardings=(psh, None, bsh, None))
                p2, o2, loss = jitted(pf, of, batch, jnp.ones((C,), jnp.float32))
            losses[kind] = float(loss)
            assert np.isfinite(losses[kind])
        # same data, same init => same loss regardless of aggregation kind
        vals = list(losses.values())
        assert max(vals) - min(vals) < 1e-4, losses
        print("FEDSTEP-OK")
    """)
    assert "FEDSTEP-OK" in out


def test_dryrun_single_combo_on_debug_scale():
    """Exercise the dry-run path end to end at 8-device scale."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import Transformer
        from repro.launch.mesh import make_debug_mesh
        from repro.launch import inputs as inp
        from repro.launch.steps import make_serve_step
        from repro.sharding import param_specs, use_mesh
        from repro.launch.hlo_stats import collective_bytes, cost_summary
        cfg = get_config("debug-dense")
        mesh = make_debug_mesh()
        model = Transformer(cfg)
        with use_mesh(mesh):
            step = make_serve_step(model)
            params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            cache = inp.cache_shapes(model, 8, 64)
            psh = param_specs(params_shape, mesh, fsdp=cfg.fsdp)
            csh = inp.cache_shardings(cache, mesh)
            jitted = jax.jit(step, in_shardings=(psh, csh, None, None))
            lowered = jitted.lower(params_shape, cache,
                                   jax.ShapeDtypeStruct((8, 1), jnp.int32),
                                   jax.ShapeDtypeStruct((), jnp.int32))
            compiled = lowered.compile()
        cs = cost_summary(compiled)
        assert cs.get("flops", 0) > 0
        stats = collective_bytes(compiled.as_text())
        print("DRYRUN-OK", stats.get("total_collective_bytes", 0))
    """)
    assert "DRYRUN-OK" in out
