"""The training loop of a cell: closed loop, one step after another.

Set-up builds the program's jitted step and state through
``launch/train.py:init_train``, puts the benchmark's seeded weights in the state, and drives that same step
through its first ``check_steps`` steps on the program's own data stream.
Those steps are read for the comparison. The window then goes on with the
same object, step after step, as ``train/loop.py:run_training`` does: each
step's batch from the stream, the step, and a wait on its metrics.

After the window the program's state is freed and the plain reference trains
``check_steps`` steps from the same weights on the benchmark's own copy of
the batches. Compared: each step's loss, each leaf's first gradient as the
optimizer got it (from its first moment after step 1), and each leaf's change
over the checked steps.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import data as bench_data
import harness as H
from refs import common as C


def ref_step_fn(fam, m: dict, hp: dict, prec: str, rows: int, block: int = 512):
    """The reference's AdamW step on the mean token loss of the first
    ``rows`` rows, a row at a time and the head a block of positions at a
    time, so that it fits beside its state."""

    def row_nll(p, tokens, labels):
        x = fam.hidden(p, tokens, m, prec)
        b = min(block, x.shape[0])
        xb = x.reshape(-1, b, x.shape[-1])
        lb = labels.reshape(-1, b)

        def body(acc, xl):
            s, c = jax.checkpoint(
                lambda p, x, l: C.nll_sum(fam.head(p, x, m, prec), l))(p, *xl)
            return (acc[0] + s, acc[1] + c), None

        (s, c), _ = jax.lax.scan(body, (0.0, 0), (xb, lb))
        return s, c

    def loss(p, tokens, labels):
        def body(acc, tl):
            s, c = jax.checkpoint(row_nll)(p, *tl)
            return (acc[0] + s, acc[1] + c), None

        (s, c), _ = jax.lax.scan(body, (0.0, 0), (tokens[:rows], labels[:rows]))
        return s / c

    def step(p, opt, tokens, labels):
        lval, g = jax.value_and_grad(loss)(p, tokens, labels)
        p, opt, clipped = C.adamw_step(p, g, opt, hp)
        norms = {k: jnp.sqrt(jnp.sum(v * v)) for k, v in C.leaves(clipped).items()}
        return p, opt, lval, norms

    return jax.jit(step, donate_argnums=(0, 1))


def ref_readings(fam, m, hp, gen, key, batches, prec="f32", rows=None):
    """Losses, first clipped gradient norms and the change's norms per leaf
    of the reference trained on ``batches``."""
    rows = rows or batches[0]["tokens"].shape[0]
    step = ref_step_fn(fam, m, hp, prec, rows)
    p = gen(key)
    opt = C.adamw_init(p)
    losses, grads = [], None
    for b in batches:
        p, opt, lval, norms = step(p, opt, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
        losses.append(float(lval))
        if grads is None:
            grads = {k: float(v) for k, v in norms.items()}
    H.free(opt)
    change = diff_norms(p, gen(key))
    H.free(p)
    return {"loss": losses, "grad": grads, "change": change}


def diff_norms(a, b) -> dict:
    return C.leaf_norms(jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(a, b))


def compare(prog: dict, ref: dict) -> dict:
    """The numbers read from a run against the reference: the losses' gap
    (the worst of the checked steps, and the first step's), and over the
    leaves the gaps of the first gradient's and of the change's norms (the
    worst leaf's and the median leaf's). Leaves whose reference gradient is
    under a thousandth of the median leaf's move by round-off alone and are
    left out of the change."""
    med = float(np.median(list(ref["grad"].values())))
    moved = {k for k, v in ref["grad"].items() if v >= 1e-3 * med}
    out = {"loss_gap": max(H.rel_gap(a, b) for a, b in zip(prog["loss"], ref["loss"])),
           "loss1_gap": H.rel_gap(prog["loss"][0], ref["loss"][0])}
    for name, key, keep in (("grad", "grad", None), ("update", "change", moved)):
        gaps = H.leaf_gaps(prog[key], ref[key], keep)
        worst = max(gaps, key=gaps.get)
        out[f"{name}_gap"] = gaps[worst]
        out[f"{name}_gap_median"] = float(np.median(list(gaps.values())))
        out[f"{name}_gap_p90"] = float(np.quantile(list(gaps.values()), 0.9))
        print(f"worst {name} leaf {worst}: program {prog[key][worst]!r} reference "
              f"{ref[key][worst]!r}", file=sys.stderr)
    print(f"losses {prog['loss']} reference {ref['loss']}; left out of the change: "
          f"{sorted(set(ref['grad']) - moved)}", file=sys.stderr)
    return out


def run(job: H.Job, fam, counts) -> H.Result:
    from repro.config import ModelConfig, RunConfig, ShapeConfig, TrainConfig
    from repro.data.synthetic import LMStream
    from repro.launch.train import init_train

    tr, m = job.traffic, job.config["model"]
    hp = dict(tr["train"])
    # the reference's model: the configuration as run, with the residual's
    # precision that it states
    m_ref = dict(m, residual_in_fp32=job.config.get("residual_in_fp32", True))
    n_check = tr["check_steps"]
    assert n_check < hp["warmup_steps"], "the reference's schedule covers warm-up only"
    res = H.Result("train")
    spans = H.Spans()
    batch, seq = tr["batch"], tr["seq"]
    res.counts = {"flops_per_step": counts.train_flops_per_token(m, seq) * batch * seq}

    run_cfg = RunConfig(model=ModelConfig(**m),
                        shape=ShapeConfig(job.cell, seq, batch, "train"),
                        train=TrainConfig(seed=job.seed & 0x7FFFFFFF,
                                          **{k: v for k, v in hp.items()
                                             if k != "no_weight_decay"}))
    devices = jax.devices()[:1]
    key = C.seed_key(job.seed)
    step, state = init_train(run_cfg, None)
    shardings = jax.tree.map(lambda a: a.sharding, state["params"])
    want = jax.tree.map(lambda a: (a.shape, a.dtype), state["params"])
    H.free(state["params"])
    gen = jax.jit(lambda k: fam.make_params(m, k), out_shardings=shardings)
    got = jax.eval_shape(gen, key)
    if jax.tree.map(lambda a: (a.shape, a.dtype), got) != want:
        raise SystemExit("the benchmark's weights do not match the program's "
                         "parameter tree")
    state = {"params": gen(key), "opt": state["opt"]}
    # one chain for every run, and the run's seed picks its rows, as a host
    # index does: some chains drawn per seed widen every precision's gap
    # alike, five to six times (PERF.md)
    stream = LMStream(m["vocab_size"], seq, batch, seed=tr["stream_seed"], host=job.seed)

    def feed(i):
        with spans("chipbench.next_batch"):
            return {k: jnp.asarray(v) for k, v in stream.batch_at(i).items()}

    def one(i):
        nonlocal state
        b = feed(i)
        with spans("chipbench.dispatch"):
            state, metrics = step(state, b)
        with spans("chipbench.wait"):
            return float(metrics["loss"])

    # the first steps: compile, warm up, and the readings compared
    prog = {"loss": []}
    profile = H.Profile(job.trace)
    for i in range(n_check):
        if i == n_check - 1:
            profile.start()
        prog["loss"].append(one(i))
        if i == 0:
            prog["grad"] = {k: v / (1 - hp["beta1"]) for k, v in
                            C.leaf_norms(state["opt"]["mu"]).items()}
    prog["change"] = diff_norms(state["params"], gen(key))
    spans.totals.clear()

    # the window
    losses, step_s, step_cpu = [], [], []
    i = n_check
    H.settle()
    t_start = time.perf_counter()
    res.e2e["setup_s"] = t_start - job.t0
    with H.window(res, profile, spans):
        while True:
            t, c = time.perf_counter(), time.thread_time()
            losses.append(one(i))
            step_s.append(time.perf_counter() - t)
            step_cpu.append(time.thread_time() - c)
            i += 1
            if job.trace:
                if len(losses) >= tr["trace_steps"]:
                    break
            elif time.perf_counter() - t_start >= job.seconds:
                break
    elapsed = time.perf_counter() - t_start
    slow = int(np.argmax(step_s))
    print(f"step seconds: median {float(np.median(step_s))!r} min {min(step_s)!r}; "
          f"slowest {step_s[slow]!r}, the main thread's CPU in it {step_cpu[slow]!r}",
          file=sys.stderr)
    res.steps = len(losses)
    res.host = dict(spans.totals)
    res.e2e["train_tokens_per_s"] = res.steps * batch * seq / elapsed
    res.attempted = res.steps
    res.failed = sum(not np.isfinite(x) for x in losses)
    res.memory_peak_bytes = H.peak_bytes(devices)
    H.free(state)
    del state

    # the comparison, after the window, from the benchmark's own data
    mine = bench_data.Stream(m["vocab_size"], seq, batch, tr["stream_seed"], job.seed)
    batches = [mine.batch_at(i) for i in range(n_check)]
    same = all(np.array_equal(b[k], stream.batch_at(j)[k])
               for j, b in enumerate(batches) for k in b)
    ref = ref_readings(fam, m_ref, hp, gen, key, batches)
    readings = dict(compare(prog, ref), data_mismatch=0.0 if same else 1.0)
    print(f"readings {readings}", file=sys.stderr)
    for name in job.limits:  # the limits file names the numbers compared
        res.check(name, readings[name], job.limits)
    if job.calibrate:
        res.calibration = calibrate(fam, m_ref, hp, gen, key, batches, ref, prog)
    return res


def calibrate(fam, m, hp, gen, key, batches, ref, prog) -> dict:
    """Readings of the control and of a planted fault, each put in the
    program's place and compared with the reference as the program is:
    the reference at fp8; the reference on half of each batch. Where the
    configuration keeps a bf16 residual, also the program against a
    reference that keeps it in float32, which shows what the residual's
    precision accounts for."""
    out = {}
    if not m.get("residual_in_fp32", True):
        wide = ref_readings(fam, dict(m, residual_in_fp32=True), hp, gen, key, batches)
        out["program_vs_fp32_residual"] = compare(prog, wide)
    out["control_fp8"] = compare(ref_readings(fam, m, hp, gen, key, batches, "fp8"), ref)
    half = batches[0]["tokens"].shape[0] // 2
    out["fault_half_batch"] = compare(
        ref_readings(fam, m, hp, gen, key, batches, rows=half), ref)
    return out
