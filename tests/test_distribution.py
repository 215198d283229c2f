"""Sharded-vs-single-device consistency: the strongest check that the
sharding rules (TP + FSDP + EP + vocab/embedding shard_maps) don't change
the math.  Runs in a subprocess so the 4-device host platform doesn't leak
into other tests (the dry-run brief forbids a global device-count override).
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs import get_smoke_config
from repro.models import init_model, model_fwd, ModelCtx
from repro.parallel.sharding import param_shardings, batch_sharding
from repro.launch.steps import model_state_shapes

for arch in ["llama3_2_1b", "dbrx_132b", "rwkv6_7b", "jamba_1_5_large_398b"]:
    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        # capacity competition is dispatch-group-dependent by design; uncap
        # it so local and EP dispatch drop nothing and must agree exactly
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    params = init_model(jax.random.PRNGKey(0), cfg)
    B, T = 4, 16
    batch = {"tokens": jnp.arange(B * T).reshape(B, T) % cfg.vocab}
    if cfg.frontend == "vision":
        batch["patch_feats"] = jnp.full(
            (B, cfg.frontend_len, cfg.frontend_dim), 0.1, jnp.float32)
    if cfg.enc_dec:
        batch["enc_feats"] = jnp.full(
            (B, cfg.frontend_len, cfg.frontend_dim), 0.1, jnp.float32)

    ref = model_fwd(params, batch, cfg=cfg)["logits"]

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ctx = ModelCtx(mesh=mesh, model_axis="model")
    p_shard = param_shardings(jax.eval_shape(lambda: params), mesh)
    params_s = jax.device_put(params, p_shard)
    batch_s = {k: jax.device_put(v, batch_sharding(mesh, v.shape))
               for k, v in batch.items()}
    with mesh:
        out = jax.jit(lambda p, b: model_fwd(p, b, cfg=cfg, ctx=ctx)["logits"])(
            params_s, batch_s)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1e-9
    assert err / scale < 5e-3, (arch, err, scale)
    print(f"OK {arch}: sharded == single-device (rel {err/scale:.2e})")

    if cfg.moe is not None:
        # full-mesh EP path (the hillclimb lever) must agree too
        ctx2 = ModelCtx(mesh=mesh, model_axis="model", ep_full=True)
        p_shard2 = param_shardings(jax.eval_shape(lambda: params), mesh,
                                   moe_full_ep=True)
        params_s2 = jax.device_put(params, p_shard2)
        with mesh:
            out2 = jax.jit(lambda p, b: model_fwd(p, b, cfg=cfg,
                                                  ctx=ctx2)["logits"])(
                params_s2, batch_s)
        err2 = float(jnp.max(jnp.abs(out2.astype(jnp.float32) -
                                     ref.astype(jnp.float32))))
        assert err2 / scale < 5e-3, (arch, "ep_full", err2, scale)
        print(f"OK {arch}: full-mesh EP == single-device (rel {err2/scale:.2e})")
print("ALL-OK")
"""


@pytest.mark.slow
def test_sharded_forward_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "ALL-OK" in r.stdout
