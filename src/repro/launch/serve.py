"""Batched serving driver: prefill + decode with KV/SSM caches.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \\
        --batch 4 --prompt-len 32 --gen 32

Runs a continuous-batch of requests through prefill, then step-decodes
with greedy sampling.  The same ``decode_step`` is what the decode_32k /
long_500k dry-run cells lower at production shapes.

``--compiler myia`` serves the Myia-compiled LM through the serving
runtime (``repro.serve``): requests are admitted into power-of-two shape
buckets, the KV/prefix cache is threaded *functionally* through the
compiled decode graph as a tuple carry, and compiled programs persist in
the AOT program cache (``--cache-dir``) — a warm process restart replays
the serialized executables with zero recompilation.  Decode is O(T):
one single-token specialization per bucket, not one per generated
length.  ``--full-prefix`` keeps the old O(T²) full-prefix-recompute
path (one specialization per length) as the differential oracle;
``--check-oracle`` runs both and asserts the token streams are
identical.  Under ``--data-mesh``/``--model-mesh`` > 1 the full-prefix
path runs the train-side LM as a per-shard program under ``shard_map``
(the SPMD tier), unchanged.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.launch.cache import checkout_path, enable_compile_cache
from repro.models import decode_step, init_params, prefill


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument(
        "--compiler",
        default="jax",
        choices=("jax", "myia"),
        help="jax: cached prefill/decode; myia: the serving runtime over "
        "the optimized+fused graph (bucketed continuous batching + AOT "
        "program cache); add --full-prefix for the per-length oracle path",
    )
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument(
        "--full-prefix",
        action="store_true",
        help="myia: serve by full-prefix recompute (the pre-runtime path; "
        "one specialization per generated length) instead of the engine",
    )
    ap.add_argument(
        "--check-oracle",
        action="store_true",
        help="myia: run the engine AND the full-prefix oracle, assert "
        "identical token streams",
    )
    ap.add_argument("--slots", type=int, default=4, help="myia: engine batch lanes")
    ap.add_argument("--min-bucket", type=int, default=32)
    ap.add_argument(
        "--cache-dir",
        default=checkout_path("artifacts", "progcache"),
        help="myia: persistent AOT program cache directory ('' disables)",
    )
    ap.add_argument(
        "--deadline",
        type=float,
        default=None,
        help="myia: per-request deadline in seconds (requests past it "
        "finish with status 'timeout', partial tokens kept)",
    )
    ap.add_argument(
        "--max-queue",
        type=int,
        default=None,
        help="myia: admission-control bound on queued requests; submits "
        "past it are rejected with reason 'queue_full' instead of queued",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="OUT.json",
        help="myia: record compile + per-request lifecycle spans and write "
        "a Chrome trace-event file (open in https://ui.perfetto.dev); also "
        "prints one telemetry summary line per request",
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        metavar="FILE",
        help="myia: after the run, write the engine's metrics registry "
        "plus the serve/cache stats snapshot as Prometheus text exposition "
        "(scrape-file / node_exporter textfile-collector format)",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)

    if args.compiler == "myia":
        if args.full_prefix or args.data_mesh * args.model_mesh > 1:
            return _serve_myia_full_prefix(args, cfg)
        return _serve_myia_engine(args, cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.gen

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32
    )
    extras = {}
    if cfg.enc_dec:
        extras["enc_frames"] = jnp.asarray(
            rng.standard_normal((args.batch, 64, cfg.d_model)), cfg.cdtype
        )
    if cfg.cross_attn_period and not cfg.enc_dec:
        extras["image_embeds"] = jnp.asarray(
            rng.standard_normal((args.batch, cfg.num_image_tokens, cfg.d_model)), cfg.cdtype
        )

    t0 = time.monotonic()
    prefill_jit = jax.jit(lambda p, t: prefill(cfg, p, t, max_len, batch_extras=extras))
    logits, caches = prefill_jit(params, prompts)
    logits.block_until_ready()
    t_prefill = time.monotonic() - t0

    decode_jit = jax.jit(lambda p, tok, pos, c: decode_step(cfg, p, tok, pos, c))
    out_tokens = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t1 = time.monotonic()
    for i in range(args.gen):
        out_tokens.append(tok)
        logits, caches = decode_step_jit_call(decode_jit, params, tok, args.prompt_len + i, caches)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tok.block_until_ready()
    t_decode = time.monotonic() - t1

    gen = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    print(f"prefill: {args.batch}×{args.prompt_len} tokens in {t_prefill:.3f}s")
    print(
        f"decode:  {args.gen} steps × batch {args.batch} in {t_decode:.3f}s "
        f"({args.gen * args.batch / max(t_decode, 1e-9):.1f} tok/s)"
    )
    print("sample generations (token ids):")
    for row in gen[:2]:
        print("  ", row[:16].tolist())
    return 0


def decode_step_jit_call(decode_jit, params, tok, pos, caches):
    return decode_jit(params, tok, jnp.int32(pos), caches)


def _serve_myia_engine(args, cfg) -> int:
    """The serving runtime: bucketed continuous batching, incremental
    decode (tuple-carried KV cache), persistent AOT program cache."""
    from repro.core.jax_backend import ProgramCache
    from repro.obs import trace as obs_trace
    from repro.serve import ServeEngine, ServeLMDims, init_serve_params, oracle_generate
    from repro.serve.engine import request_telemetry

    tracer = obs_trace.Tracer() if args.trace else None
    dims = ServeLMDims.from_config(cfg)
    params = init_serve_params(dims, jax.random.PRNGKey(0))
    cache = ProgramCache(args.cache_dir) if args.cache_dir else None
    engine = ServeEngine(
        dims,
        params,
        n_slots=args.slots,
        min_bucket=args.min_bucket,
        program_cache=cache,
        default_deadline_s=args.deadline,
        max_queue=args.max_queue,
        trace=tracer,
    )

    rng = np.random.default_rng(0)
    submitted = []
    for _ in range(args.batch):
        prompt = rng.integers(0, dims.vocab, args.prompt_len).tolist()
        submitted.append((engine.submit(prompt, args.gen), prompt))

    t0 = time.monotonic()
    results = engine.run()
    wall = time.monotonic() - t0

    stats = engine.stats()
    ttfts = [r["ttft_s"] for r in results.values() if r["ttft_s"] is not None]
    ttft_txt = f"ttft {min(ttfts) * 1e3:.1f}ms" if ttfts else "ttft n/a"
    print(
        f"[myia/engine] {args.batch} reqs × (prompt {args.prompt_len} + gen "
        f"{args.gen}) in {wall:.3f}s ({stats['tokens_generated'] / max(wall, 1e-9):.1f} tok/s, "
        f"{ttft_txt})"
    )
    print(
        f"[myia/engine] buckets {stats['buckets_in_use']}, compilations "
        f"{stats['compilations']} (floor {stats['compilation_floor']})"
    )
    print(
        f"[myia/engine] statuses {stats['statuses']}, rejected "
        f"{stats['rejected']}, queue peak {stats['queue_peak']}"
    )
    if cache is not None:
        cs = cache.stats.as_dict()
        print(f"[myia/engine] program cache: {cs}")
        degraded = {
            k: cs[k]
            for k in ("corrupt_entries", "quarantined", "compile_retries", "vm_fallbacks")
            if cs.get(k)
        }
        if degraded:
            print(f"[myia/engine] DEGRADED-MODE events: {degraded}")
    print("sample generations (token ids):")
    for rid, _prompt in submitted[:2]:
        print("  ", results[rid]["tokens"][:16])

    if tracer is not None:
        # one line per request, reconstructed purely from lifecycle spans
        tel = request_telemetry(tracer)
        for rid, _prompt in submitted:
            t = tel.get(rid)
            if t is None:
                continue
            n_tok = len(results[rid]["tokens"])
            tok_s = (
                n_tok / (t["gen_ms"] / 1e3)
                if t["gen_ms"] and n_tok
                else None
            )
            fmt = lambda v, suf="": "n/a" if v is None else f"{v:.1f}{suf}"
            print(
                f"[myia/telemetry] rid={rid} status={t['status']} "
                f"bucket={t['bucket']} ttft={fmt(t['ttft_ms'], 'ms')} "
                f"queue={fmt(t['queue_ms'], 'ms')} tok/s={fmt(tok_s)}"
            )
        tracer.write_chrome_trace(args.trace)
        print(
            f"[myia/telemetry] wrote {len(tracer.events)} spans to "
            f"{args.trace} (open in https://ui.perfetto.dev)"
        )

    if args.metrics_out:
        from repro.obs import snapshot, to_prometheus

        text = to_prometheus(
            engine.telemetry,
            extra=snapshot(
                serve={k: v for k, v in stats.items() if k != "telemetry"},
                cache=cache.stats if cache is not None else None,
            ),
        )
        with open(args.metrics_out, "w", encoding="utf-8") as f:
            f.write(text)
        print(
            f"[myia/metrics] wrote {len(text.splitlines())} exposition "
            f"lines to {args.metrics_out}"
        )

    if args.check_oracle:
        fns: dict = {}
        for rid, prompt in submitted:
            if results[rid]["status"] != "ok":
                continue  # timeout/failed streams are partial by contract
            want = oracle_generate(dims, params, prompt, args.gen, fns=fns)
            got = results[rid]["tokens"]
            assert got == want, f"engine diverged from full-prefix oracle on rid {rid}"
        print(f"[myia/engine] oracle check passed ({len(submitted)} requests)")
    return 0


def _serve_myia_full_prefix(args, cfg) -> int:
    """Greedy decode off the Myia-compiled LM forward (SPMD tier when a
    mesh is active).  Batch stays data-parallel; the vocab projection is
    model-parallel — the same specs the train step uses.  Decode
    recomputes the full prefix per step (one specialization per length):
    this is the serving runtime's differential oracle."""
    from repro.launch.mesh import make_local_mesh
    from repro.launch.myia_step import (
        MyiaLMDims,
        build_lm_logits,
        init_lm_params,
        lm_in_specs,
    )
    from repro.core import api
    from repro.parallel import mesh_context

    dims = MyiaLMDims.from_config(cfg)
    params = init_lm_params(dims, jax.random.PRNGKey(0))
    logits_fn = api.myia(
        build_lm_logits(dims),
        options=api.CompileOptions(fuse=True, in_specs=lm_in_specs(with_labels=False)),
    )

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, dims.vocab, (args.batch, args.prompt_len)), jnp.int32
    )
    use_mesh = args.data_mesh * args.model_mesh > 1
    mesh = make_local_mesh(args.data_mesh, args.model_mesh) if use_mesh else None

    with mesh_context(mesh, {}):
        t0 = time.monotonic()
        logits = logits_fn(*params, tokens)
        jax.block_until_ready(logits)
        t_prefill = time.monotonic() - t0
        out_tokens = []
        t1 = time.monotonic()
        for i in range(args.gen):
            tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            out_tokens.append(tok)
            if i + 1 == args.gen:
                break  # the last sample needs no further forward pass
            tokens = jnp.concatenate([tokens, tok[:, None]], axis=1)
            logits = logits_fn(*params, tokens)
        if out_tokens:
            jax.block_until_ready(out_tokens[-1])
        t_decode = time.monotonic() - t1

    tier = "shard_map" if mesh is not None else "single-device"
    print(f"[myia/{tier}] prefill: {args.batch}×{args.prompt_len} in {t_prefill:.3f}s")
    print(
        f"[myia/{tier}] decode: {args.gen} steps × batch {args.batch} in "
        f"{t_decode:.3f}s (full-prefix recompute, one specialization per length)"
    )
    if out_tokens:
        gen = np.stack([np.asarray(t) for t in out_tokens], axis=1)
        print("sample generations (token ids):")
        for row in gen[:2]:
            print("  ", row[:16].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
