"""Sharding-rule unit tests + an 8-device SPMD integration test.

The multi-device test runs in a subprocess so the main pytest process keeps
the single real host device (per the dry-run isolation requirement)."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding


class _FakeMesh:
    """Just enough Mesh surface for the spec-assignment logic."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)

    @property
    def size(self):
        n = 1
        for v in self.shape.values():
            n *= v
        return n


def test_param_specs_rules():
    mesh = _FakeMesh({"data": 16, "model": 16})
    spec = sharding._spec_for(
        ["layers", "attn", "q", "w"], (22, 2048, 2048), mesh, False
    )
    assert spec == P(None, None, "model")
    spec = sharding._spec_for(
        ["layers", "attn", "o", "w"], (22, 2048, 2048), mesh, False
    )
    assert spec == P(None, "model", None)
    spec = sharding._spec_for(
        ["layers", "mlp", "experts", "gate", "w"], (16, 64, 2048, 1024), mesh, False
    )
    assert spec == P(None, "model", None, None)
    spec = sharding._spec_for(["embed"], (32000, 2048), mesh, False)
    assert spec == P("model", None)
    spec = sharding._spec_for(["layers", "ln1", "scale"], (22, 2048), mesh, False)
    assert spec == P(None, None)
    # optimizer-state mirror keeps the same layout
    spec = sharding._spec_for(
        ["opt", "m", "layers", "attn", "q", "w"], (22, 2048, 2048), mesh, False
    )
    assert spec == P(None, None, "model")


def test_param_specs_divisibility_guard():
    mesh = _FakeMesh({"data": 16, "model": 16})
    # vocab 256206 % 16 != 0 -> replicated, not an error
    spec = sharding._spec_for(["embed"], (256206, 1024), mesh, False)
    assert spec == P(None, None)


def test_fsdp_adds_data_axis():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    spec = sharding._spec_for(
        ["layers", "mlp", "gate", "w"], (22, 2048, 5632), mesh, True
    )
    assert spec == P(None, ("pod", "data"), "model")


def test_sharder_guard_on_small_dims():
    mesh = sharding.make_mesh((1, 1), ("data", "model"))
    shard = sharding.make_sharder(mesh)
    x = jnp.ones((4, 8, 16))
    y = shard(x, ("batch", "seq", None))
    assert y.shape == x.shape


@pytest.mark.slow
def test_spmd_8dev_train_step_runs():
    """Real SPMD execution on 8 fake host devices (subprocess)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import base as cfgbase
        from repro.distributed import sharding
        from repro.launch import steps as steps_lib
        from repro.optim.adamw import AdamW

        mesh = sharding.make_mesh((4, 2), ("data", "model"))
        arch = cfgbase.get("tinyllama_1_1b")
        model, cfg = steps_lib.build_model(arch, smoke=True)
        shard = sharding.make_sharder(mesh)
        params = model.init(jax.random.key(0))
        pspecs = sharding.param_specs(params, mesh)
        pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                              is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(params, pshard)
        opt = AdamW(warmup_steps=1, total_steps=4)
        state = {"params": params, "opt": opt.init(params)}
        step_fn = jax.jit(steps_lib.make_train_step(model, opt, shard),
                          donate_argnums=0)
        batch = {
            "inputs": jax.device_put(
                jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab),
                NamedSharding(mesh, P("data"))),
            "targets": jax.device_put(
                jax.random.randint(jax.random.key(2), (8, 32), 0, cfg.vocab),
                NamedSharding(mesh, P("data"))),
        }
        losses = []
        for _ in range(3):
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
        assert all(jnp.isfinite(jnp.asarray(losses))), losses
        assert losses[-1] < losses[0], losses   # same batch -> must descend
        print("SPMD8 OK", losses)
    """)
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=repo,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SPMD8 OK" in out.stdout


def test_elastic_mesh_builder():
    from repro.distributed import fault_tolerance as ft

    mesh = ft.healthy_device_mesh()
    assert mesh.size == len(jax.devices())
