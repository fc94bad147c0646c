"""Runtime tests: checkpoint manager, data pipeline, optimizer, gradient
compression, train driver loss decrease."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import TokenStream
from repro.distributed import compression as comp
from repro.optim.adamw import AdamW, cosine_schedule


# ----------------------------------------------------------- checkpoint --
def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "b": {"c": jnp.ones((4,), jnp.bfloat16)},
    }
    mgr = CheckpointManager(tmp_path, keep_n=2)
    mgr.save(3, tree, {"note": "x"})
    like = jax.tree.map(jnp.zeros_like, tree)
    restored, step, meta = mgr.restore(like)
    assert step == 3 and meta["note"] == "x"
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert restored["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_gc_and_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_n=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.asarray(s)})
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(7, {"x": jnp.ones((128, 128))}, blocking=False)
    mgr.wait()
    restored, step, _ = mgr.restore({"x": jnp.zeros((128, 128))})
    assert step == 7
    assert float(restored["x"].sum()) == 128 * 128


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, {"x": jnp.ones((4,))})
    with pytest.raises(ValueError):
        mgr.restore({"x": jnp.zeros((5,))})


# ------------------------------------------------------------- pipeline --
def test_token_stream_deterministic_and_sharded():
    s = TokenStream(vocab_size=128, seq_len=32, global_batch=8, seed=1)
    b1, b2 = s.batch(5), s.batch(5)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert not np.array_equal(np.asarray(s.batch(6)["tokens"]),
                              np.asarray(b1["tokens"]))
    h0 = s.shard_for_host(b1, 0, 2)
    h1 = s.shard_for_host(b1, 1, 2)
    recon = np.concatenate([h0["tokens"], h1["tokens"]], axis=0)
    np.testing.assert_array_equal(recon, np.asarray(b1["tokens"]))


def test_labels_shifted():
    s = TokenStream(vocab_size=128, seq_len=16, global_batch=2, seed=0)
    b = s.batch(0)
    np.testing.assert_array_equal(
        np.asarray(b["labels"][:, :-1]), np.asarray(b["tokens"][:, 1:])
    )
    assert np.all(np.asarray(b["labels"][:, -1]) == -1)


# ------------------------------------------------------------ optimizer --
def test_adamw_reduces_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}  # d/dw ||w||^2
        params, state, _ = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_adamw_clipping_and_schedule():
    sched = cosine_schedule(1.0, warmup=10, total=100)
    assert float(sched(jnp.asarray(0))) == 0.0
    assert abs(float(sched(jnp.asarray(10))) - 1.0) < 1e-6
    assert float(sched(jnp.asarray(100))) <= 0.2
    opt = AdamW(lr=0.1, clip_norm=1.0)
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    _, _, metrics = opt.update({"w": jnp.full(3, 100.0)}, state, params)
    assert float(metrics["grad_norm"]) > 100


# ---------------------------------------------------------- compression --
def test_quantize_roundtrip_small_error():
    x = jax.random.normal(jax.random.PRNGKey(0), (256,))
    q, s = comp.quantize(x)
    err = np.abs(np.asarray(comp.dequantize(q, s) - x))
    assert err.max() <= float(s) * 0.51 + 1e-9


def test_error_feedback_unbiased_over_time():
    """Sum of EF-compressed grads converges to sum of raw grads."""
    key = jax.random.PRNGKey(1)
    grads_seq = [jax.random.normal(jax.random.fold_in(key, i), (64,)) * 0.1
                 for i in range(50)]
    state = comp.init_ef_state({"g": grads_seq[0]})
    total_comp = jnp.zeros(64)
    for g in grads_seq:
        qtree, state = comp.ef_compress_tree({"g": g}, state)
        total_comp = total_comp + comp.dequantize(*qtree["g"])
    total_raw = sum(grads_seq)
    # residual bounds the gap: |sum_comp - sum_raw| == |residual|
    gap = np.abs(np.asarray(total_comp - total_raw))
    res = np.abs(np.asarray(state.residual["g"]))
    np.testing.assert_allclose(gap, res, atol=1e-5)
    assert gap.max() < 0.01  # one quantization step, not 50


def test_compressed_training_still_converges():
    """AdamW on a quadratic with int8 EF gradients reaches the optimum."""
    opt = AdamW(lr=0.05, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0, 1.0])}
    state = opt.init(params)
    ef = comp.init_ef_state(params)
    for _ in range(300):
        grads = {"w": 2 * params["w"]}
        qtree, ef = comp.ef_compress_tree(grads, ef)
        deq = comp.ef_decompress_tree(qtree, grads)
        params, state, _ = opt.update(deq, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.1


# ------------------------------------------------------------ training --
@pytest.mark.slow
def test_train_loop_decreases_loss(tmp_path):
    from repro.launch.train import main as train_main

    loss_end = train_main([
        "--arch", "qwen1_5_0_5b", "--smoke", "--steps", "60",
        "--seq-len", "64", "--batch", "4", "--lr", "3e-3",
        "--warmup", "5", "--ckpt-dir", str(tmp_path / "ck"),
    ])
    # loss after 60 steps on patterned data well below ln(512)=6.24 init
    assert loss_end < 5.9


@pytest.mark.slow
def test_train_restart_resumes(tmp_path):
    from repro.launch.train import main as train_main

    ck = str(tmp_path / "ck")
    args = ["--arch", "qwen1_5_0_5b", "--smoke", "--seq-len", "32",
            "--batch", "2", "--lr", "1e-3", "--ckpt-dir", ck,
            "--ckpt-every", "10"]
    loss_full = train_main(args + ["--steps", "30"])
    # interrupted run: 30 steps in one go == 20 then resume to 30
    ck2 = str(tmp_path / "ck2")
    args2 = [a if a != ck else ck2 for a in args]
    train_main(args2 + ["--steps", "20"])
    loss_resumed = train_main(args2 + ["--steps", "30"])
    assert abs(loss_full - loss_resumed) < 1e-4


@pytest.mark.slow
def test_compressed_psum_multidevice_subprocess():
    """compressed_psum_grads inside shard_map on 8 fake devices: the
    summed gradient matches the uncompressed psum within int8 tolerance."""
    import json as _json
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, json
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compression as comp

        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 0.1

        def body(g_blk):
            grads = {"w": g_blk[0]}
            state = comp.init_ef_state(grads)
            summed, state = comp.compressed_psum_grads(grads, state, "data")
            return summed["w"]

        fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P(), check_vma=False)
        got = fn(g)
        want = jnp.sum(g, axis=0)
        err = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(g))) / 127 * 8
        print(json.dumps({"err": err, "tol": scale}))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo", timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = _json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["err"] <= rec["tol"] + 1e-6, rec


@pytest.mark.slow
def test_elastic_restart_on_fewer_devices():
    """Checkpoints are layout-free: a run sharded over 8 devices restores
    and continues on 4 (elastic scale-down after pod loss)."""
    import json as _json
    import subprocess
    import sys
    import tempfile
    import textwrap

    tmp = tempfile.mkdtemp()
    code_tpl = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import jax, json
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager
        from repro.configs import registry
        from repro.data.pipeline import make_batch_fn
        from repro.models import build_model
        from repro.optim.adamw import AdamW, make_train_step

        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh(({n},), ("data",))
        cfg = registry.get_smoke_config("qwen1_5_0_5b")
        model = build_model(cfg)
        opt = AdamW(lr=1e-3)
        step_fn = jax.jit(make_train_step(model, opt))
        batch_fn = make_batch_fn(cfg, 32, 8)
        mgr = CheckpointManager("{tmp}")
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        start = 0
        if mgr.latest_step() is not None:
            tree, start, _ = mgr.restore(
                {{"params": params, "opt": opt_state}})
            params, opt_state = tree["params"], tree["opt"]
        # shard the batch over however many devices exist now
        shard = NamedSharding(mesh, P("data"))
        for s in range(start, start + 5):
            batch = jax.tree.map(
                lambda x: jax.device_put(x, shard), batch_fn(s))
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        mgr.save(start + 5, {{"params": params, "opt": opt_state}})
        print(json.dumps({{"loss": float(metrics["loss"]),
                           "devices": {n}, "end": start + 5}}))
    """
    outs = []
    for n in (8, 4):  # scale DOWN mid-run
        code = textwrap.dedent(code_tpl.format(n=n, tmp=tmp))
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                 "HOME": "/root", "JAX_PLATFORMS": "cpu"},
            cwd="/root/repo", timeout=420,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        outs.append(_json.loads(out.stdout.strip().splitlines()[-1]))
    assert outs[0]["end"] == 5 and outs[1]["end"] == 10
    assert np.isfinite(outs[1]["loss"])
    # training continued productively after the elastic restart
    assert outs[1]["loss"] < 6.5
