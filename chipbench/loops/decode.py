"""The decode loop of a cell: a closed batch of requests streaming greedy
tokens to the host.

Set-up makes the benchmark's seeded weights and the prompts, and writes each
row's prompt into the program's cache through the program's decode step
(``train/steps.py:make_decode_step``, jitted with the cache donated), a chunk
of ``prefill_chunk`` tokens per call. The last chunk's greedy token starts
every request. In the window each step feeds the previous token back and
sends the new one to the host; a request ends after ``gen_len`` tokens, and
the row is then asked again from the end of its prompt.

After the window the program's state is freed and the plain reference runs
once over a sample of the finished requests, drawn from the seed: each
prompt with its served tokens. Compared: the widest gap by which a served
token's reference logit lies below the reference's best at its position.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness as H
from refs import common as C


def ref_logits(fam, m, params, rows, positions, prec="f32"):
    """Reference logits (len(positions), V) for each row of tokens."""
    fwd = jax.jit(lambda p, t: fam.head(p, fam.hidden(p, t, m, prec)[positions], m, prec))
    return [fwd(params, jnp.asarray(r)) for r in rows]


def widest_gap(logits, served) -> float:
    """max over positions of (best reference logit - served token's logit)."""
    g = jnp.max(logits, -1) - jnp.take_along_axis(logits, jnp.asarray(served)[:, None], -1)[:, 0]
    return float(jnp.max(g))


def run(job: H.Job, fam, counts) -> H.Result:
    from repro.config import ModelConfig, RunConfig, ServeConfig, ShapeConfig
    from repro.models import api
    from repro.train.steps import make_decode_step

    tr, m = job.traffic, job.config["model"]
    b, cache_len, plen, glen = tr["batch"], tr["cache_len"], tr["prompt_len"], tr["gen_len"]
    chunk = tr["prefill_chunk"]
    assert plen % chunk == 0 and plen + glen <= cache_len
    res = H.Result("decode")
    spans = H.Spans()
    cfg = ModelConfig(**m)
    run_cfg = RunConfig(model=cfg, shape=ShapeConfig(job.cell, cache_len, b, "decode"),
                        serve=ServeConfig(kv_dtype=tr["kv_dtype"]))
    step_fn, _, _, _ = make_decode_step(run_cfg, None)
    decode = jax.jit(step_fn, donate_argnums=(1,))
    device = jax.devices()[0]

    # every input committed to the chip, so that each call finds the same
    # compiled program
    def put(x):
        return jax.device_put(x, device)

    key = C.seed_key(job.seed)
    gen = jax.jit(lambda k: fam.make_params(m, k), out_shardings=put(0).sharding)
    params = gen(key)
    prompts = np.asarray(jax.random.randint(jax.random.fold_in(key, 1), (b, plen), 0,
                                            m["vocab_size"], jnp.int32))
    cache = put(jax.jit(lambda: api.init_cache(cfg, b, cache_len, tr["kv_dtype"]))())
    for c in range(plen // chunk):
        first, _, cache = decode(params, cache, put(prompts[:, c * chunk:(c + 1) * chunk]),
                                 put(jnp.int32(c * chunk)))
    index = [put(jnp.int32(plen + t)) for t in range(glen)]
    # warm the one-token step; the window writes the same position again
    profile = H.Profile(job.trace)
    profile.start()
    _, _, cache = jax.block_until_ready(decode(params, cache, first, index[0]))

    def serve_step(tok, t):
        nonlocal cache
        with spans("chipbench.dispatch"):
            nxt, _, cache = decode(params, cache, tok, index[t])
        with spans("chipbench.fetch_tokens"):
            host = np.asarray(nxt)
        return nxt, host

    first_host = np.asarray(first)
    requests = []  # each finished round: (rows, gen_len + 1) tokens served
    gaps, gap_cpu = [], []
    tok, t, cur = first, 0, [first_host]
    H.settle()
    t_start = time.perf_counter()
    res.e2e["setup_s"] = t_start - job.t0
    last, last_cpu = t_start, time.thread_time()
    with H.window(res, profile, spans):
        while True:
            tok, host = serve_step(tok, t)
            now, now_cpu = time.perf_counter(), time.thread_time()
            gaps.append(now - last)
            gap_cpu.append(now_cpu - last_cpu)
            last, last_cpu = now, now_cpu
            cur.append(host)
            t += 1
            if t == glen:
                requests.append(np.concatenate(cur, axis=1))
                tok, t, cur = first, 0, [first_host]
            if job.trace:
                if len(gaps) >= tr["trace_steps"]:
                    break
            elif now - t_start >= job.seconds:
                break
    elapsed = last - t_start
    slow = int(np.argmax(gaps))
    print(f"token gaps (s): median {float(np.median(gaps))!r}; longest {gaps[slow]!r}, "
          f"the main thread's CPU in it {gap_cpu[slow]!r}", file=sys.stderr)
    steps = len(gaps)
    res.steps = steps
    res.host = dict(spans.totals)
    res.e2e["decode_tokens_per_s"] = steps * b / elapsed
    res.e2e["decode_token_p95_ms"] = 1e3 * float(np.quantile(gaps, 0.95, method="linear"))
    # required work per step, averaged over the positions the window used
    pos = [plen + (i % glen) + 1 for i in range(steps)]
    need = counts.decode_step(m, b, float(np.mean(pos)), jnp.dtype(tr["kv_dtype"]).itemsize)
    res.counts = {"flops_per_step": need["flops"], "bytes_per_step": need["bytes"]}
    res.attempted = len(requests) * b
    res.memory_peak_bytes = H.peak_bytes([device])
    H.free((params, cache, tok, first))

    # the comparison: a sample of finished requests, drawn from the seed
    if not requests:
        res.check("no_request_finished", 1.0, {"no_request_finished": 0.0})
        return res
    rng = np.random.default_rng(np.random.SeedSequence([job.seed, 7]))
    pick = rng.choice(len(requests) * b, size=min(tr["check_requests"], len(requests) * b),
                      replace=False)
    served = [requests[i // b][i % b] for i in pick]
    rows = [np.concatenate([prompts[i % b], s[:-1]]) for i, s in zip(pick, served)]
    positions = np.arange(plen - 1, plen + glen)
    ref_params = gen(key)
    ref = ref_logits(fam, m, ref_params, rows, positions)
    gaps_req = [widest_gap(lg, s) for lg, s in zip(ref, served)]
    res.failed = sum(g > job.limits.get("logit_gap", float("inf")) for g in gaps_req)
    res.check("logit_gap", max(gaps_req), job.limits)
    if job.calibrate:
        res.calibration = calibrate(fam, m, ref_params, rows, positions, ref, served, rng)
    return res


def calibrate(fam, m, params, rows, positions, ref, served, rng) -> dict:
    """The control: at each position, the gap of the token that the
    reference at fp8 puts first. The fault: one served token per request
    replaced by another drawn from the seed."""
    low = ref_logits(fam, m, params, rows, positions, "fp8")
    control = max(widest_gap(r, jnp.argmax(lo, -1)) for r, lo in zip(ref, low))
    faults = []
    for r, s in zip(ref, served):
        bad = np.array(s)
        j = rng.integers(len(bad))
        bad[j] = (bad[j] + 1 + rng.integers(m["vocab_size"] - 1)) % m["vocab_size"]
        faults.append(widest_gap(r, bad))
    return {"control_fp8": {"logit_gap": control},
            "fault_token_altered": {"logit_gap": max(faults)}}
