"""Bring-up smoke on a TPU: the Myia-compiled train step and the serve
engine at the full widths of ``internlm2-1.8b``.

    python chip_smoke.py              # one chip: device, train, serve
    python chip_smoke.py --chips 4    # four chips: only the sharded train
                                      # step on a 2x2 mesh vs one device

Phases (one process — a chip belongs to one process at a time):

* **device** — platform, device kind and count.  No TPU: the script says
  so and exits 1 without printing a result.
* **train** — ``make_myia_train_step(..., fuse=True)`` at vocab 92544,
  d_model 2048, d_hidden 8192, batch 8 x seq 256, fed by the same
  ``SyntheticLM`` data ``repro.launch.train --compiler myia`` uses.  The
  compiled step must hold at least one Pallas kernel
  (``tpu_custom_call``), and its step-0 loss and gradients must match a
  plain ``jax.value_and_grad`` of a jnp spelling of the same loss.  Then
  three SGD steps.
* **serve** — a ``ServeEngine`` at the same widths over a fresh AOT
  program cache: 4 requests of prompt 32 + gen 16 must all end ``ok``
  and the prefill logits must match a jnp reference; a second engine on
  the same cache must load every program from disk with no XLA compile.
* **sharded** (``--chips 4`` only) — the same train step on a 2x2
  ``data``/``model`` mesh (``lm_in_specs``) must take the SPMD tier and
  agree with the single-device step.

Both sides of every comparison run at the backend's default matmul
precision (on a TPU one bf16 pass with f32 accumulation); the tolerances
below are set for that.  Weights and data are random, made from
``--seed``.  Device memory at these widths (described-v5e compile): the
fused loss+gradient program takes 1.65 GB of arguments, 1.65 GB of
outputs and 0.83 GB of temporaries, so the batch is not cut.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "internlm2-1.8b"
BATCH, SEQ, STEPS = 8, 256, 3
LR = 3e-4  # repro.launch.train's default
N_REQUESTS, PROMPT_LEN, GEN_LEN = 4, 32, 16

#: relative error of the scalar loss against the jnp reference
LOSS_RTOL = 1e-3
#: relative L2 error of each gradient / of the prefill logits
REL_L2_TOL = 1e-2


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def ref_lm_loss(emb, w1, w2, wout, tokens, labels):
    """jnp spelling of ``repro.launch.myia_step.build_lm_loss``."""
    import jax
    import jax.numpy as jnp

    h = jnp.take(emb, tokens, axis=0)
    h = jnp.tanh(h @ w1)
    h = jnp.tanh(h @ w2)
    logp = jax.nn.log_softmax(h @ wout, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def ref_prefill_logits(params, tokens, cmask):
    """jnp spelling of ``repro.serve.model.build_prefill``'s logits."""
    import jax
    import jax.numpy as jnp

    emb, wq, wk, wv, w1, w2, wout = params
    h0 = jnp.take(emb, tokens, axis=0)
    q, k, v = h0 @ wq, h0 @ wk, h0 @ wv
    s = (q @ jnp.swapaxes(k, -1, -2)) * (1.0 / float(np.sqrt(emb.shape[1])))
    p = jax.nn.softmax(jnp.where(cmask, s, -jnp.inf), axis=-1)
    h = jnp.tanh((h0 + p @ v) @ w1)
    return jnp.tanh(h @ w2) @ wout


def compare_step(tag: str, got, want) -> None:
    """``got``/``want`` are ``(loss, (d_emb, d_w1, d_w2, d_wout))``."""
    loss_err = abs(float(got[0]) - float(want[0])) / abs(float(want[0]))
    errs = [rel_l2(g, w) for g, w in zip(got[1], want[1])]
    log(
        f"[{tag}] loss {float(got[0]):.6f} vs {float(want[0]):.6f} "
        f"(rel err {loss_err:.2e}, tol {LOSS_RTOL}); gradient rel L2 "
        + ", ".join(
            f"{n} {e:.2e}" for n, e in zip(("emb", "w1", "w2", "wout"), errs)
        )
        + f" (tol {REL_L2_TOL})"
    )
    check(loss_err <= LOSS_RTOL, f"{tag}: loss off by {loss_err:.2e}")
    check(max(errs) <= REL_L2_TOL, f"{tag}: gradient off by {max(errs):.2e}")


def train_batch(dims, seed: int, step: int):
    import jax.numpy as jnp

    from repro.data import DataConfig, SyntheticLM

    ds = SyntheticLM(
        DataConfig(vocab=dims.vocab, seq_len=SEQ, global_batch=BATCH, seed=seed)
    )
    return {k: jnp.asarray(v) for k, v in ds.batch(step).items()}


def train_phase(dims, seed: int) -> None:
    import jax

    from repro.kernels import get_kernel_mode
    from repro.launch.myia_step import make_myia_train_step

    mode = get_kernel_mode()
    log(
        f"[train] {ARCH}: vocab {dims.vocab}, d_model {dims.d_model}, "
        f"d_hidden {dims.d_hidden}; batch {BATCH} x seq {SEQ}; kernel mode {mode}"
    )
    check(mode == "pallas", f"kernel mode is {mode!r}, not 'pallas'")
    step_fn, init_fn = make_myia_train_step(dims, BATCH, SEQ, lr=LR, fuse=True)
    vag = step_fn.vag
    state = init_fn(jax.random.PRNGKey(seed))
    b0 = train_batch(dims, seed, 0)
    args = (*state["params"], b0["tokens"], b0["labels"])

    clusters = vag.explain(*args).as_dict()["fusion"]["clusters"]
    for c in clusters:
        why = f" ({c['reason']['kind']}: {c['reason']['detail']})" if "reason" in c else ""
        log(f"[train] cluster {c['cluster']} {c['kind']} {c['verdict']} {c.get('name', '')}{why}")
    n_emitted = sum(c["verdict"] == "emitted" for c in clusters)
    log(f"[train] clusters emitted {n_emitted}, declined {len(clusters) - n_emitted}")

    t0 = time.perf_counter()
    got = jax.block_until_ready(vag(*args))
    first_call_s = time.perf_counter() - t0
    runner = vag.specialize(args)
    check(bool(getattr(runner, "lowered", False)), "train step did not lower")
    compiled = runner.jitted.lower(*args).compile()
    mem = compiled.memory_analysis()
    log(
        f"[train] compiled loss+gradient: arguments {mem.argument_size_in_bytes}, "
        f"outputs {mem.output_size_in_bytes}, temporaries {mem.temp_size_in_bytes} bytes"
    )
    n_kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    log(f"[train] runner.lowered True; tpu_custom_call in compiled HLO: {n_kernels}")
    check(n_kernels >= 1, "no Pallas kernel in the compiled train step")

    want = jax.block_until_ready(
        jax.jit(jax.value_and_grad(ref_lm_loss, argnums=(0, 1, 2, 3)))(*args)
    )
    compare_step("train", got, want)
    del got, want
    gc.collect()

    losses, times = [], []
    for step in range(STEPS):
        t0 = time.perf_counter()
        state, metrics = step_fn(state, train_batch(dims, seed, step))
        losses.append(float(metrics["loss"]))  # waits for the step
        times.append(time.perf_counter() - t0)
    log(
        f"[train] compile: first call (tier-0) {first_call_s:.2f} s, "
        f"first step (full-opt jit + update) {times[0]:.2f} s; "
        f"later steps " + ", ".join(f"{t:.3f} s" for t in times[1:])
    )
    log(f"[train] losses {losses}")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"[train] peak device memory {stats['peak_bytes_in_use']} bytes")


def serve_phase(dims, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.jax_backend import ProgramCache
    from repro.launch.cache import checkout_path
    from repro.serve import ServeEngine, init_serve_params, oracle_generate
    from repro.serve.model import causal_mask

    cache_dir = checkout_path("artifacts", "chip_smoke_progcache")
    shutil.rmtree(cache_dir, ignore_errors=True)
    params = init_serve_params(dims, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, dims.vocab, PROMPT_LEN).tolist() for _ in range(N_REQUESTS)
    ]

    def serve(tag: str):
        cache = ProgramCache(cache_dir)
        engine = ServeEngine(
            dims, params, n_slots=N_REQUESTS, min_bucket=32, program_cache=cache
        )
        rids = [engine.submit(p, GEN_LEN) for p in prompts]
        t0 = time.perf_counter()
        results = engine.run()
        wall = time.perf_counter() - t0
        statuses = [results[r]["status"] for r in rids]
        cs = cache.stats.as_dict()
        log(f"[serve] {tag}: {len(rids)} requests in {wall:.2f} s, statuses {statuses}")
        log(f"[serve] {tag} program cache: {cs}")
        check(statuses == ["ok"] * len(rids), f"{tag}: not every request ended ok")
        check(
            all(len(results[r]["tokens"]) == GEN_LEN for r in rids),
            f"{tag}: a request generated the wrong number of tokens",
        )
        degraded = {
            k: cs[k]
            for k in ("vm_fallbacks", "compile_retries", "corrupt_entries", "quarantined")
            if cs[k]
        }
        check(not degraded, f"{tag}: degraded-mode counters {degraded}")
        return engine, [results[r]["tokens"] for r in rids], cs

    engine, tokens, _ = serve("cold")
    bucket = engine.buckets_in_use[0]
    ref = jax.jit(ref_prefill_logits)
    for i, prompt in enumerate(prompts):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(prompt)] = prompt
        padded = jnp.asarray(padded)
        got = engine._prefill_fn(*params, padded, causal_mask(bucket))[0]
        err = rel_l2(got, ref(params, padded, causal_mask(bucket)))
        log(f"[serve] request {i}: prefill logits rel L2 vs jnp {err:.2e} (tol {REL_L2_TOL})")
        check(err <= REL_L2_TOL, f"prefill logits of request {i} off by {err:.2e}")

    # Greedy streams agree until the first near-tie argmax that the two
    # paths' roundings break differently; from there the contexts differ.
    # The margin printed is the oracle's logit gap between its token and
    # the engine's at that first split.
    fns: dict = {}
    agree = []
    for i, (prompt, got) in enumerate(zip(prompts, tokens)):
        want = oracle_generate(dims, params, prompt, GEN_LEN, fns=fns)
        agree.append(sum(a == b for a, b in zip(got, want)))
        split = next((j for j, (a, b) in enumerate(zip(got, want)) if a != b), None)
        if split is not None:
            ctx = prompt + want[:split]
            logits = fns[len(ctx)](*params, jnp.asarray([ctx], jnp.int32), causal_mask(len(ctx)))
            row = logits[0][0, -1]
            log(
                f"[serve] request {i} splits from the oracle at token {split}: "
                f"oracle logit margin {float(row[want[split]] - row[got[split]]):.3e}"
            )
    log(
        f"[serve] token agreement with oracle_generate: {agree} of {GEN_LEN} per "
        f"request ({sum(agree)}/{GEN_LEN * len(agree)})"
    )

    _, warm_tokens, cs = serve("warm")
    check(cs["hits"] > 0 and cs["exec_loads"] > 0, "warm engine missed the cache")
    check(cs["xla_compiles"] == 0, f"warm engine compiled {cs['xla_compiles']} programs")
    check(warm_tokens == tokens, "warm engine generated different tokens")


def sharded_phase(dims, seed: int) -> None:
    import jax

    from repro.launch.mesh import make_local_mesh
    from repro.launch.myia_step import make_myia_train_step
    from repro.parallel import mesh_context

    step_fn, init_fn = make_myia_train_step(dims, BATCH, SEQ, lr=LR, fuse=True)
    vag = step_fn.vag
    state = init_fn(jax.random.PRNGKey(seed))
    b0 = train_batch(dims, seed, 0)
    args = (*state["params"], b0["tokens"], b0["labels"])

    t0 = time.perf_counter()
    single = jax.block_until_ready(vag(*args))
    log(f"[sharded] single-device step: {time.perf_counter() - t0:.2f} s with compile")
    mesh = make_local_mesh(2, 2)
    with mesh_context(mesh, {}):
        runner = vag.specialize(args)
        spmd = bool(getattr(runner, "spmd", False))
        log(f"[sharded] 2x2 data/model mesh: runner.spmd {spmd}")
        check(spmd, "the mesh run fell back to the single-device tier")
        t0 = time.perf_counter()
        sharded = jax.block_until_ready(vag(*args))
        log(f"[sharded] sharded step: {time.perf_counter() - t0:.2f} s with compile")
    compare_step("sharded", sharded, single)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="4: run only the sharded train step on a 2x2 mesh",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"[device] platform {dev.platform}, kind {dev.device_kind!r}, count {len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX found {dev.platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} found", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.launch.cache import enable_compile_cache
    from repro.launch.myia_step import MyiaLMDims

    log(f"[device] compile cache {enable_compile_cache()}")
    dims = MyiaLMDims.from_config(get_config(ARCH))
    try:
        if args.chips == 4:
            sharded_phase(dims, args.seed)
        else:
            train_phase(dims, args.seed)
            gc.collect()
            serve_phase(dims, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    result = {
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
