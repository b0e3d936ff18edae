"""Distributed-path numerics: the shard_map implementations (vocab-parallel
embed/loss, expert-parallel MoE, full train step) must match the
single-device oracle.  Runs in a SUBPROCESS with 8 forced host devices so
the main test session keeps seeing one device.
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp
assert len(jax.devices()) == 8

from repro.configs import get_config
from repro.dist.sharding import CPU_RUNTIME, Runtime, default_rules, shardings_for_schema
from repro.models import forward_train, init_model_params, model_schema
from repro.models.moe import moe_apply_ep, moe_apply_local, moe_schema
from repro.models.layers import init_params
from repro.train.data import SyntheticLMDataset

# the LM stack relies on GSPMD propagation: Auto axes (jax.make_mesh's
# default became Explicit, under which the vocab-parallel gather has no
# unambiguous out sharding)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rt = Runtime(mesh=mesh, dp_axes=("data",), tp_axis="model")

# ---- full train forward: dense (vocab-parallel loss + embed + SP) --------
cfg = get_config("glm4-9b").reduced().with_overrides(dtype="float32")
params = init_model_params(jax.random.key(0), cfg)
data = SyntheticLMDataset(cfg.vocab_size, 32, 4, seed=0)
batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}

loss_cpu, _ = jax.jit(lambda p, b: forward_train(p, b, cfg, CPU_RUNTIME))(params, batch)
with mesh:
    p_sh = shardings_for_schema(model_schema(cfg), default_rules(), mesh)
    params_d = jax.device_put(params, p_sh)
    loss_dist, _ = jax.jit(lambda p, b: forward_train(p, b, cfg, rt))(params_d, batch)
err = abs(float(loss_cpu) - float(loss_dist))
print("dense loss cpu=%.6f dist=%.6f err=%.2e" % (loss_cpu, loss_dist, err))
assert err < 2e-4, err

# gradient parity
g_cpu = jax.grad(lambda p: forward_train(p, batch, cfg, CPU_RUNTIME)[0])(params)
with mesh:
    g_dist = jax.jit(jax.grad(lambda p: forward_train(p, batch, cfg, rt)[0]))(params_d)
gn = float(jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g_cpu))))
dn = float(jnp.sqrt(sum(jnp.sum(jnp.square(a - b)) for a, b in
                        zip(jax.tree.leaves(g_cpu), jax.tree.leaves(g_dist)))))
print("dense grad rel err %.2e" % (dn / gn))
assert dn / gn < 1e-3, (dn, gn)

# ---- expert-parallel MoE vs local ------------------------------------------
mcfg = get_config("dbrx-132b").reduced().with_overrides(dtype="float32")
msch = moe_schema(mcfg)
mp = init_params(jax.random.key(1), msch)
x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16, mcfg.d_model)),
                jnp.float32)
y_local, aux_local = moe_apply_local(mp, x, mcfg)
with mesh:
    specs = shardings_for_schema(msch, default_rules(), mesh)
    mp_d = jax.device_put(mp, specs)
    y_ep, aux_ep = jax.jit(
        lambda p, xx: moe_apply_ep(p, xx, mcfg, mesh, dp_axes=("data",),
                                   tp_axis="model")
    )(mp_d, x)
err = float(jnp.max(jnp.abs(y_local - y_ep)))
print("moe ep vs local: %.2e  aux %.4f vs %.4f" % (err, aux_local, aux_ep))
assert err < 1e-4, err
assert abs(float(aux_local) - float(aux_ep)) < 1e-4

# ---- TP flash decoding == single-device decode ----------------------------
import dataclasses
from repro.models import decode_step, init_serve_cache, prefill

rt_fd = dataclasses.replace(rt, flash_decode=True)
B, S = 2, 8
toks = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, (B, S)),
                   jnp.int32)

def serve(runtime):
    cache = init_serve_cache(cfg, B, S + 8, dtype=jnp.float32)
    _, cache = prefill(params, {"tokens": toks, "cache": cache}, cfg, runtime)
    d = {"tokens": jnp.zeros((B, 1), jnp.int32),
         "pos": jnp.full((B,), S, jnp.int32), "cache": cache}
    l2, _ = decode_step(params, d, cfg, runtime)
    return np.asarray(l2, np.float32)

l_cpu = serve(CPU_RUNTIME)
with mesh:
    l_tp = serve(rt_fd)
err = np.abs(l_cpu - l_tp).max()
print("flash_decode_tp err: %.2e" % err)
assert err < 1e-3, err

# ---- bf16-before-gather: loss parity within bf16 tolerance ----------------
from repro.train.train_step import make_train_step
from repro.train.optimizer import OptConfig, init_opt_state

cfg_bf = get_config("glm4-9b").reduced()  # bf16 compute dtype
params_bf = init_model_params(jax.random.key(0), cfg_bf)
oc = OptConfig(lr=1e-3)
with mesh:
    p_sh2 = shardings_for_schema(model_schema(cfg_bf), default_rules(), mesh)
    pd = jax.device_put(params_bf, p_sh2)
    s0 = init_opt_state(pd, oc)
    base = jax.jit(make_train_step(cfg_bf, rt, oc))
    opt = jax.jit(make_train_step(cfg_bf, rt, oc, cast_params_once=True))
    _, _, m_base = base(pd, s0, batch)
    pd2 = jax.device_put(params_bf, p_sh2)
    s02 = init_opt_state(pd2, oc)
    _, _, m_opt = opt(pd2, s02, batch)
d = abs(float(m_base["loss"]) - float(m_opt["loss"]))
print("cast_params loss delta: %.4f (base %.4f)" % (d, float(m_base["loss"])))
assert d < 0.02, d
print("DIST OK")
"""


@pytest.mark.slow
def test_distributed_matches_single_device():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=900, cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "DIST OK" in r.stdout
