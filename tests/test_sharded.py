"""Multi-device behaviour (8 fake host devices in a SUBPROCESS so the main
pytest process keeps its single real device): sharded-vs-reference numerics
for MoE EP/TPE, sharded train step, pipeline parallelism, elastic checkpoint
reshard."""
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.config import ModelConfig, RunConfig, ShapeConfig, TrainConfig, MeshConfig
    from repro.launch.mesh import make_mesh
    from repro.models import api, moe
    from repro.parallel.ctx import ParallelCtx
    from repro.train.steps import make_train_step
    from repro.train.optim import make_optimizer

    mesh = make_mesh((2, 4), ("data", "model"))
    pc = ParallelCtx(mesh=mesh, batch_axes=("data",))

    # --- MoE EP vs reference (4 experts over 4-way model axis) ---
    cfg = ModelConfig(name="m", family="moe", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=4, d_ff=64, vocab_size=64,
                      num_experts=4, num_experts_per_tok=2, moe_d_ff=32,
                      capacity_factor=8.0, compute_dtype="float32")
    params = api.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)}
    ref_logits, ref_aux = api.forward(params, batch, cfg, None)
    with jax.set_mesh(mesh):
        ep_logits, ep_aux = jax.jit(
            lambda p, b: api.forward(p, b, cfg, pc))(params, batch)
    assert moe.ep_scheme(cfg, pc) == "ep"
    err = float(jnp.max(jnp.abs(ref_logits - ep_logits)))
    assert err < 2e-3, f"EP vs ref logits err {err}"
    print("EP-vs-ref OK", err)

    # --- TPE scheme (6 experts on 4-way axis -> hidden sharding) ---
    cfg2 = ModelConfig(name="m2", family="moe", num_layers=1, d_model=32,
                       num_heads=4, num_kv_heads=4, d_ff=64, vocab_size=64,
                       num_experts=6, num_experts_per_tok=2, moe_d_ff=32,
                       capacity_factor=8.0, compute_dtype="float32")
    assert moe.ep_scheme(cfg2, pc) == "tpe"
    p2 = api.init(jax.random.PRNGKey(0), cfg2)
    r2, _ = api.forward(p2, batch, cfg2, None)
    with jax.set_mesh(mesh):
        s2, _ = jax.jit(lambda p, b: api.forward(p, b, cfg2, pc))(p2, batch)
    err2 = float(jnp.max(jnp.abs(r2 - s2)))
    assert err2 < 2e-3, f"TPE vs ref err {err2}"
    print("TPE-vs-ref OK", err2)

    # --- sharded train step runs + loss matches unsharded ---
    dcfg = ModelConfig(name="d", family="dense", num_layers=2, d_model=32,
                       num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                       compute_dtype="float32")
    run = RunConfig(model=dcfg, shape=ShapeConfig("t", 16, 4, "train"),
                    train=TrainConfig(total_steps=10, warmup_steps=1,
                                      microbatches=2),
                    mesh=MeshConfig(fsdp_min_size=1))
    tb = {"tokens": batch["tokens"],
          "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64)}
    step_ref, _, _ = make_train_step(run, None)
    dparams = api.init(jax.random.PRNGKey(0), dcfg)
    opt = make_optimizer(run.train)
    state = {"params": dparams, "opt": opt.init(dparams)}
    _, m_ref = jax.jit(step_ref)(state, tb)
    with jax.set_mesh(mesh):
        step_sh, sspecs, bspecs = make_train_step(run, pc)
        # bare PartitionSpecs resolve against the mesh set above, as in
        # launch/train.py --mesh
        jstep = jax.jit(step_sh, in_shardings=(sspecs, bspecs),
                        out_shardings=(sspecs, None))
        new_state, m_sh = jstep(state, tb)
    dl = abs(float(m_ref["loss"]) - float(m_sh["loss"]))
    assert dl < 0.02, f"sharded vs ref loss diff {dl}"
    print("sharded train step OK", dl)

    # --- elastic checkpoint reshard: save sharded, restore to 1 device ---
    import tempfile
    from repro.train import checkpoint as ckpt
    d = tempfile.mkdtemp()
    ckpt.save(d, 1, new_state)
    restored, _ = ckpt.restore(d, new_state)
    for a, b_ in zip(jax.tree.leaves(new_state), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-6)
    print("elastic reshard OK")

    # --- pipeline parallelism on a 8-stage mesh ---
    from repro.parallel.pipeline import pipeline_apply
    pmesh = make_mesh((8,), ("stage",))
    S = 8
    ws = jax.random.normal(jax.random.PRNGKey(3), (S, 16, 16)) * 0.3
    xs = jax.random.normal(jax.random.PRNGKey(4), (6, 4, 16))  # M=6 microbatches
    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])
    out = pipeline_apply(stage_fn, {"w": ws}, xs, pmesh)
    ref = xs
    for s in range(S):
        ref = jnp.tanh(ref @ ws[s])
    err3 = float(jnp.max(jnp.abs(out - ref)))
    assert err3 < 1e-5, f"pipeline err {err3}"
    print("pipeline OK", err3)
    print("ALL-SHARDED-OK")
""")


def test_sharded_suite_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL-SHARDED-OK" in r.stdout
