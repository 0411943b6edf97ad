"""Sharding rules + a tiny-mesh jit of reduced models under those rules."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_arch, supports_shape
from repro.distributed.sharding import (batch_shardings, fsdp_enabled,
                                        param_shardings, state_shardings)
from repro.kvcache.cache import decode_state_shapes
from repro.launch.mesh import make_local_mesh
from repro.models import build_model


def test_fsdp_threshold():
    assert fsdp_enabled(get_arch("nemotron-4-340b"))
    assert fsdp_enabled(get_arch("yi-34b"))
    assert not fsdp_enabled(get_arch("smollm-360m"))


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_param_shardings_cover_every_leaf(name):
    cfg = get_arch(name)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    mesh = make_local_mesh()
    sh = param_shardings(shapes, cfg, mesh)
    n_shapes = len(jax.tree.leaves(shapes))
    n_sh = len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")))
    assert n_shapes == n_sh


def test_state_shardings_long_context_batch1():
    cfg = get_arch("hymba-1.5b")
    mesh = make_local_mesh()
    shapes = decode_state_shapes(cfg, 1, 4096)
    sh = state_shardings(shapes, cfg, mesh, batch=1)
    assert len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec"))) == \
        len(jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple)))


def test_jit_under_local_mesh_with_rules():
    """End-to-end: shard a reduced model's params per the rules on a 1x1 mesh
    named like production and run a loss step."""
    cfg = dataclasses.replace(get_arch("internlm2-1.8b").reduced(), dtype="float32")
    model = build_model(cfg)
    mesh = make_local_mesh()
    params = model.init(jax.random.PRNGKey(0))
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    sh = param_shardings(shapes, cfg, mesh)
    params = jax.tree.map(lambda a, s: jax.device_put(a, s), params, sh)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "targets": jnp.zeros((2, 16), jnp.int32),
             "loss_mask": jnp.ones((2, 16), jnp.float32)}
    b_sh = batch_shardings(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch),
        cfg, mesh)
    with jax.set_mesh(mesh):
        loss = jax.jit(model.loss, in_shardings=(sh, b_sh))(params, batch)
    assert bool(jnp.isfinite(loss))


def test_supports_shape_matrix():
    """Exactly the 8 pure-attention archs skip long_500k (32 runnable cells)."""
    runnable = skipped = 0
    for name in ARCHS:
        for sname, shape in SHAPES.items():
            ok, reason = supports_shape(get_arch(name), shape)
            if ok:
                runnable += 1
            else:
                skipped += 1
                assert sname == "long_500k"
                assert get_arch(name).family not in ("ssm", "hybrid")
    assert runnable == 32 and skipped == 8      # 40 total cells


def test_logical_constraint_needs_a_mesh_and_raises_inside_one():
    """Installed rules are a no-op with no mesh active; inside a mesh a
    constraint the mesh cannot satisfy raises instead of being dropped."""
    from repro.models import common
    from repro.models.common import logical_constraint, set_logical_rules
    x = jnp.ones((4, 8))
    prev = common._LOGICAL_RULES
    set_logical_rules({"batch": "nope"})
    try:
        assert logical_constraint(x, "batch", None) is x
        with jax.set_mesh(make_local_mesh()):
            with pytest.raises(Exception, match="nope"):
                jax.jit(lambda a: logical_constraint(a, "batch", None))(x)
    finally:
        set_logical_rules(prev)
