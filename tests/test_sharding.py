"""Sharding rules: spec validity on abstract meshes + distributed equivalence
(subprocess with 8 forced host devices, so this process keeps 1 device)."""
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED_CONFIGS, get_config
from repro.distributed.sharding import batch_spec, cache_specs, param_specs
from repro.models import build_model


def _abstract_mesh(multi_pod=False):
    if multi_pod:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.mark.parametrize("arch", sorted(ASSIGNED_CONFIGS))
@pytest.mark.parametrize("multi_pod", [False, True])
def test_param_specs_divisible(arch, multi_pod):
    """Every assigned spec dim must divide by its mesh axis size."""
    cfg = get_config(arch, param_dtype="bfloat16", compute_dtype="bfloat16")
    model = build_model(cfg)
    params_shape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    mesh = _abstract_mesh(multi_pod)
    specs = param_specs(params_shape, mesh)
    flat_p = jax.tree_util.tree_leaves(params_shape)
    flat_s = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    n_model_sharded = 0
    for leaf, spec in zip(flat_p, flat_s):
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            total = int(np.prod([mesh.shape[a] for a in axes]))
            assert leaf.shape[dim] % total == 0, (leaf.shape, spec)
            if "model" in axes:
                n_model_sharded += 1
    # the bulk of parameters must actually be model-sharded
    assert n_model_sharded >= len(flat_p) // 4


def test_batch_spec_divisibility_fallbacks():
    mesh = _abstract_mesh(multi_pod=True)   # pod*data = 32
    assert batch_spec(mesh, 256, 2)[0] == ("pod", "data")
    # PartitionSpec normalises a 1-tuple to the bare axis name
    assert batch_spec(mesh, 16, 2)[0] in ("data", ("data",))
    assert batch_spec(mesh, 1, 2)[0] is None


@pytest.mark.parametrize("arch", ["internlm2-20b", "jamba-1.5-large-398b", "xlstm-125m"])
def test_cache_specs_valid(arch):
    cfg = get_config(arch, param_dtype="bfloat16", compute_dtype="bfloat16")
    model = build_model(cfg)
    mesh = _abstract_mesh()
    B = 128
    cache = jax.eval_shape(lambda: model.init_cache(B, 1024))
    specs = cache_specs(cache, mesh, B)
    for leaf, spec in zip(jax.tree_util.tree_leaves(cache),
                          jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            total = int(np.prod([mesh.shape[a] for a in axes]))
            assert leaf.shape[dim] % total == 0, (leaf.shape, spec)


_DISTRIBUTED_SCRIPT = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # libtpu may be installed: never probe TPU
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import build_model
from repro.distributed.sharding import (param_specs, batch_spec, named,
                                        make_mesh as compat_make_mesh)
from repro.training.optimizer import AdamWConfig, AdamWState, init_adamw
from repro.training.train import TrainState, make_train_step

cfg = get_config("granite-3-2b", reduced=True, d_model=256, n_heads=4, n_kv_heads=2,
                 vocab_size=512, d_ff=512)
model = build_model(cfg)
opt_cfg = AdamWConfig()
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, 512, (8, 32)), jnp.int32)}
step = make_train_step(model, opt_cfg)

# single-device reference
state0 = TrainState(params=model.init_params(jax.random.PRNGKey(0)),
                    opt=init_adamw(model.init_params(jax.random.PRNGKey(0)), opt_cfg))
ref_state, ref_metrics = jax.jit(step)(state0, batch)

# distributed
mesh = compat_make_mesh((2, 4), ("data", "model"))
pspecs = param_specs(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)), mesh)
sspecs = TrainState(params=pspecs, opt=AdamWState(step=P(), mu=pspecs, nu=pspecs))
bspec = {"tokens": batch_spec(mesh, 8, 2)}
state_d = jax.device_put(state0, named(sspecs, mesh))
batch_d = jax.device_put(batch, named(bspec, mesh))
with mesh:
    dist_state, dist_metrics = jax.jit(
        step, in_shardings=(named(sspecs, mesh), named(bspec, mesh)),
        out_shardings=(named(sspecs, mesh),
                       jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), ref_metrics)),
    )(state_d, batch_d)

# sharded chunked-CE reductions reorder f32 sums; match the 2e-3 rel
# tolerance the parameter comparison below already uses
ref_loss, dist_loss = float(ref_metrics["loss"]), float(dist_metrics["loss"])
assert abs(ref_loss - dist_loss) < 2e-3 * max(abs(ref_loss), 1.0), \
    (ref_loss, dist_loss)
for a, b in zip(jax.tree_util.tree_leaves(ref_state.params),
                jax.tree_util.tree_leaves(dist_state.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(jax.device_get(b)),
                               rtol=2e-3, atol=2e-3)
print("DISTRIBUTED_MATCH")
"""


def test_distributed_train_step_matches_single_device():
    res = subprocess.run([sys.executable, "-c", _DISTRIBUTED_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert "DISTRIBUTED_MATCH" in res.stdout, res.stdout + res.stderr
