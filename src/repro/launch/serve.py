"""Serving entrypoint.

  python -m repro.launch.serve --arch qwen2-1.5b [--batch 4] [--new-tokens 16]
  python -m repro.launch.serve --arch qwen2-1.5b --continuous [--qps 20]

Serves the registered config at its published widths; ``--smoke`` serves
the reduced CPU toy instead.  Default mode runs the fixed-batch engine on
host devices: batched prefill + greedy decode through the sharded KV
cache.  ``--continuous`` serves a
generated Poisson request trace through the paged continuous-batching
engine (lanes refilled mid-decode, pages allocated/freed per request) and
reports per-request latency percentiles.
"""
from __future__ import annotations

import argparse


def _run_fixed(args):
    import jax
    import numpy as np

    from repro.configs import run_config
    from repro.models.api import get_model
    from repro.serve.engine import ServingEngine

    cfg = run_config(args.arch, smoke=args.smoke)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, args.batch, args.capacity)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32
    )
    out = eng.generate(prompts, args.new_tokens)
    print(f"generated {out.shape} tokens")
    print(f"prefill {eng.stats.prefill_s*1e3:.1f} ms, "
          f"decode {eng.stats.tokens_per_s:.1f} tokens/s "
          f"({eng.stats.steps_per_s:.1f} steps/s)")


def _run_continuous(args):
    import jax

    from repro.configs import run_config
    from repro.models.api import get_model
    from repro.serve.engine import ContinuousBatchingEngine
    from repro.serve.scheduler import ContinuousScheduler
    from repro.serve.trace import generate_request_trace, materialize_requests

    cfg = run_config(args.arch, smoke=args.smoke)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(
        cfg, params, lanes=args.batch, n_pages=args.pages,
        page_tokens=args.page_tokens, lane_capacity=args.capacity,
    )
    trace = generate_request_trace(
        args.requests, seed=7, qps=args.qps,
        vocab_size=min(512, cfg.vocab_size),
        prompt_len=(4, args.prompt_len),
        max_new=(4, args.new_tokens), name="cli",
    )
    reqs = materialize_requests(trace)
    rep = ContinuousScheduler(eng).run(reqs)
    print(f"served {len(rep.completed)} requests in {rep.makespan:.2f}s "
          f"virtual ({rep.tokens_out()} tokens, "
          f"{eng.stats.tokens_per_s:.1f} tokens/s decode)")
    print(f"latency p50 {rep.latency_percentile(50)*1e3:.1f} ms, "
          f"p99 {rep.latency_percentile(99)*1e3:.1f} ms; "
          f"deferrals: {rep.page_deferrals} page")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's reduced CPU toy, not its "
                         "published widths")
    ap.add_argument("--batch", type=int, default=4,
                    help="batch rows (fixed) / decode lanes (continuous)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=64,
                    help="KV capacity per row/lane (tokens)")
    ap.add_argument("--continuous", action="store_true",
                    help="serve a Poisson request trace with continuous batching")
    ap.add_argument("--qps", type=float, default=20.0)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--pages", type=int, default=33)
    ap.add_argument("--page-tokens", type=int, default=8)
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    if args.continuous:
        _run_continuous(args)
    else:
        _run_fixed(args)


if __name__ == "__main__":
    main()
