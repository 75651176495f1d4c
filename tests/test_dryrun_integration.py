"""Dry-run machinery integration test at reduced scale: lower + compile a
smoke arch on an 8-device fake mesh with the production sharding rules, and
check the collective census parser on the compiled HLO."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # spawns an 8-fake-device lowering subprocess

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys
    sys.path.insert(0, sys.argv[1])
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke_config
    from repro.distributed.sharding import make_rules, shardings as ctx
    from repro.launch import specs as specs_lib
    from repro.launch import steps as steps_lib
    from repro.launch.dryrun import collective_census
    from repro.launch.mesh import make_mesh_shape
    from repro.models.model import build_model
    from repro.optim.optimizer import Optimizer

    mesh = make_mesh_shape((2, 4))
    arch = sys.argv[2]
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    rules = make_rules(mesh, cfg=cfg, fsdp=True)

    p_structs = steps_lib.param_structs(model.meta)
    p_sh = steps_lib.param_shardings(mesh, rules, model.meta)
    replicated = NamedSharding(mesh, P())
    B, S = 8, 32
    batch = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    if cfg.n_image_tokens:
        batch["images"] = jax.ShapeDtypeStruct(
            (B, cfg.n_image_tokens, cfg.frontend_feat_dim), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq, cfg.frontend_feat_dim), jnp.float32)
    batch_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, P("data", *([None] * (len(s.shape) - 1)))),
        batch)

    opt = Optimizer.create("adamw", lr=1e-3, parametrization=model.p13n,
                           meta=model.meta, weight_decay=0.1)
    step = steps_lib.make_train_step(model, opt)
    o_structs = steps_lib.opt_state_structs(opt, p_structs)
    o_sh = steps_lib.opt_state_shardings(mesh, rules, model.meta, opt, replicated)
    with ctx(mesh, rules):
        lowered = jax.jit(
            step, in_shardings=(p_sh, o_sh, batch_sh),
            out_shardings=(p_sh, o_sh, replicated),
        ).lower(p_structs, o_structs, batch)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    assert cost.get("flops", 0) > 0
    census = collective_census(compiled.as_text())
    # FSDP + TP must produce collectives
    assert census["total"] > 0, census
    print("DRYRUN_OK", arch, int(cost["flops"]), census["total"])
    """
)


def _run(arch):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, src, arch],
        capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DRYRUN_OK" in out.stdout


def test_dryrun_dense_arch():
    _run("gemma2-2b")


def test_dryrun_moe_arch():
    _run("mixtral-8x22b")


def test_dryrun_ssm_arch():
    _run("mamba2-130m")


def test_collective_census_parser():
    from repro.launch.dryrun import collective_census

    hlo = """
      %ar = f32[128,256]{1,0} all-reduce(f32[128,256] %x), replica_groups={}
      %ag = bf16[64,32] all-gather(bf16[8,32] %y), dimensions={0}
      %rs.1 = f32[16] reduce-scatter(f32[128] %z), dimensions={0}
      %cp = u8[4] collective-permute(u8[4] %w)
    """
    c = collective_census(hlo)
    assert c["all-reduce"] == 2 * 128 * 256 * 4  # x2 ring weighting
    assert c["all-gather"] == 64 * 32 * 2
    assert c["reduce-scatter"] == 16 * 4
    assert c["collective-permute"] == 4
    assert c["total"] == sum(
        v for k, v in c.items() if k != "total"
    )
