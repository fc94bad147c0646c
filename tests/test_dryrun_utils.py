"""Dry-run tooling tests: collective-byte parser, sharding rule engine,
and a miniature (8 fake devices) lower+compile in a subprocess."""
import json
import subprocess
import sys
import textwrap

import pytest


def test_parse_collective_bytes():
    from repro.launch.dryrun import parse_collective_bytes

    hlo = textwrap.dedent("""
      %all-gather.3 = bf16[4,1024,512]{2,1,0} all-gather(%p0), dimensions={0}
      %all-reduce.1 = f32[256,128]{1,0} all-reduce(%p1), to_apply=%sum
      %reduce-scatter.2 = f32[16,64]{1,0} reduce-scatter(%p2), dimensions={0}
      %all-to-all.9 = bf16[8,80,7168]{2,1,0} all-to-all(%p3), dimensions={0}
      %collective-permute.4 = u32[2]{0} collective-permute(%p4)
      %add.5 = f32[2]{0} add(%x, %y)
    """)
    totals, counts = parse_collective_bytes(hlo)
    assert counts["all-gather"] == 1
    assert totals["all-gather"] == 4 * 1024 * 512 * 2
    assert totals["all-reduce"] == 2 * 256 * 128 * 4  # 2x ring weight
    assert totals["reduce-scatter"] == 16 * 64 * 4
    assert totals["all-to-all"] == 8 * 80 * 7168 * 2
    assert totals["collective-permute"] == 2 * 4
    assert counts["all-reduce"] == 1


def test_parse_ignores_non_collectives():
    from repro.launch.dryrun import parse_collective_bytes

    totals, counts = parse_collective_bytes(
        "%dot.1 = f32[128,128]{1,0} dot(%a, %b)\n"
    )
    assert sum(counts.values()) == 0


def test_rule_engine_divisibility_fallback():
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as sh
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((1, 1), ("data", "model"))

    eng = sh.RuleEngine(mesh)
    # both divide trivially on a unit mesh
    ns = eng.spec("x", ("data", "model"), (8, 16))
    assert ns.spec == P("data", "model")


def test_param_shardings_cover_all_leaves():
    import jax

    from repro.configs import registry
    from repro.distributed import sharding as sh
    from repro.launch.mesh import auto_mesh
    from repro.models import build_model

    mesh = auto_mesh((1, 1), ("data", "model"))
    for aid in ("qwen1_5_0_5b", "arctic_480b", "jamba_1_5_large_398b",
                "seamless_m4t_medium"):
        cfg = registry.get_smoke_config(aid)
        model = build_model(cfg)
        specs = model.param_specs()
        shardings, fallbacks = sh.param_shardings(mesh, specs, cfg)
        n_leaves = len(jax.tree.leaves(specs))
        n_shard = len(jax.tree.leaves(
            shardings, is_leaf=lambda x: hasattr(x, "spec")
        ))
        assert n_leaves == n_shard


@pytest.mark.slow
def test_mini_dryrun_subprocess(tmp_path):
    """Full lower+compile path on 8 placeholder devices (fast analogue of
    the 512-device production dry-run; exercises env-flag ordering)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, json
        import jax.numpy as jnp
        from repro.configs import registry
        from repro.distributed import api as dist_api, sharding as sh
        from repro.models import build_model
        from repro.optim.adamw import AdamW, make_train_step

        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((4, 2), ("data", "model"))
        cfg = registry.get_smoke_config("internlm2_20b")
        model = build_model(cfg)
        pspecs = model.param_specs()
        p_shard, _ = sh.param_shardings(mesh, pspecs, cfg)
        opt = AdamW()
        ospecs = jax.eval_shape(opt.init, pspecs)
        o_sh, _ = sh.param_shardings(mesh, ospecs.m, cfg)
        o_shard = type(ospecs)(m=o_sh, v=o_sh,
            step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
        batch = {
            "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32),
        }
        b_shard = sh.batch_shardings(mesh, batch)
        fn = make_train_step(model, opt)
        flops = {}
        for sp in (False, True):  # baseline + sequence-parallel rules
            rules = sh.activation_rule_table(mesh, cfg, seq_parallel=sp)
            with jax.set_mesh(mesh), dist_api.activation_rules(rules, mesh=mesh,
                                                 dp_axes=("data",)):
                compiled = jax.jit(
                    fn, in_shardings=(p_shard, o_shard, b_shard),
                    out_shardings=(p_shard, o_shard, None)
                ).lower(pspecs, ospecs, batch).compile()
            flops[sp] = float(compiled.cost_analysis().get("flops", 0))
        print(json.dumps({"flops": flops[False], "flops_sp": flops[True]}))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo", timeout=420,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["flops_sp"] > 0  # SP rule table lowers too
