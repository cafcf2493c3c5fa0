"""Serving example: batched requests through the continuous-batching engine.

Loads the newest checkpoint written by examples/train_lm.py for the
same ``--preset`` if present (else random init), admits a batch of
prompts, and decodes greedily — the same prefill/decode_step programs
the decode_32k/long_500k dry-run cells lower at 512 devices.

  PYTHONPATH=src python examples/serve_lm.py
  PYTHONPATH=src python examples/serve_lm.py --preset tiny
  PYTHONPATH=src python examples/serve_lm.py --temperature 0.8 --seed 7
  PYTHONPATH=src python examples/serve_lm.py --splits 6 \\
      --warm-cache-dir /tmp/serve-cache   # 2nd run skips re-tracing
"""

import argparse

import jax
import numpy as np

from repro.configs.base import get_config
from repro.models.lm import Model
from repro.serve.engine import Engine, Request
from repro.train import checkpoint as CK
from repro.train.optimizer import AdamW

from train_lm import PRESETS, ckpt_dir_for  # noqa: E402  (same presets)


def main(argv=None):
    """Serve 4 requests; returns the finished requests."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="reduced")
    ap.add_argument("--max-new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature "
                         "(0 = greedy, the default)")
    ap.add_argument("--seed", type=int, default=0,
                    help="per-request sampling seed (temperature>0)")
    ap.add_argument("--latency-target-s", type=float, default=None,
                    help="per-request latency target; drives the edf "
                         "scheduler and the latency-slack telemetry")
    ap.add_argument("--scheduler-policy", choices=("fifo", "edf"),
                    default="fifo")
    ap.add_argument("--kv-layout", choices=("paged", "dense"),
                    default="paged")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV cache block size in tokens")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="split prefills into pieces of at most this "
                         "many tokens (default: whole prompt)")
    ap.add_argument("--chunk-token-budget", type=int, default=None,
                    help="pack prefill pieces from multiple requests "
                         "into waves of at most this many tokens")
    ap.add_argument("--plan", default="",
                    help="precision-plan JSON: serve the prefill/"
                         "decode GEMMs under the tuned plan")
    ap.add_argument("--splits", type=int, default=0,
                    help="offload every GEMM at this split count "
                         "(a plain PrecisionPolicy; no plan artifact "
                         "needed — handy with --warm-cache-dir)")
    ap.add_argument("--min-dim", type=int, default=128,
                    help="with --splits: offload only GEMMs whose m, k "
                         "and n are all at least this (decode GEMMs "
                         "have m = one row per slot)")
    ap.add_argument("--warm-cache-dir", default="",
                    help="persist jaxpr-transform decisions/programs "
                         "here so a restarted server warm-starts "
                         "without re-tracing (needs --plan/--splits)")
    ap.add_argument("--keep-logits", action="store_true",
                    help="keep each emitted token's logits row on the "
                         "returned requests (Request.logits)")
    ap.add_argument("--ckpt-dir", default="",
                    help="override the per-preset checkpoint dir")
    ap.add_argument("--metrics-dir", default="",
                    help="telemetry dir (repro.obs JSONL); default: "
                         "<ckpt-dir>/metrics; 'none' disables")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve live Prometheus /metrics on this port "
                         "while the engine runs (0 = ephemeral; the "
                         "chosen port is printed)")
    ap.add_argument("--hold-metrics-s", type=float, default=0.0,
                    help="keep the /metrics endpoint up this many "
                         "seconds after decoding finishes, so an "
                         "external scraper (the CI smoke) can read "
                         "the final counters")
    args = ap.parse_args(argv)

    arch, overrides, _, _ = PRESETS[args.preset]
    cfg = get_config(arch).replace(**overrides)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ckpt_dir = args.ckpt_dir or ckpt_dir_for(args.preset)
    last = CK.latest_step(ckpt_dir)
    if last is not None:
        print(f"[serve] loading checkpoint step {last}")
        opt_like = AdamW().init(params)
        try:
            params, _ = CK.restore(ckpt_dir, last, (params, opt_like))
        except CK.CheckpointError as e:
            # Only the narrow "checkpoint absent/incompatible" case
            # falls back to random init; anything else is a real bug
            # and propagates.
            print(f"[serve] restore failed ({e}); using random init")

    plan = None
    if args.plan:
        from repro.tune import PrecisionPlan

        plan = PrecisionPlan.load(args.plan)
        print(f"[serve] precision plan {args.plan} "
              f"({plan.fingerprint}, {len(plan.sites)} sites)")
    policy = None
    if args.splits:
        from repro.core import PrecisionPolicy

        policy = PrecisionPolicy(default_splits=args.splits,
                                 min_dim=args.min_dim)
    metrics = None
    if args.metrics_dir != "none":
        from repro.obs import MetricsRun

        metrics = MetricsRun(args.metrics_dir
                             or f"{ckpt_dir}/metrics")
    engine = Engine(model, params, batch_slots=4, max_len=512,
                    plan=plan, policy=policy, metrics=metrics,
                    kv_layout=args.kv_layout,
                    block_size=args.block_size,
                    chunk_tokens=args.chunk_tokens,
                    chunk_token_budget=args.chunk_token_budget,
                    warm_cache_dir=args.warm_cache_dir or None,
                    scheduler_policy=args.scheduler_policy,
                    metrics_port=args.metrics_port)
    if engine.metrics_server is not None:
        print(f"[serve] live metrics: "
              f"{engine.metrics_server.url}/metrics", flush=True)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=[int(t) for t in
                            rng.integers(1, cfg.vocab_size, 16)],
                    max_new_tokens=args.max_new_tokens,
                    temperature=args.temperature,
                    seed=args.seed + i,
                    latency_target_s=args.latency_target_s,
                    logits=[] if args.keep_logits else None)
            for i in range(4)]
    try:
        done = engine.run(reqs)
        if engine.metrics_server is not None and args.hold_metrics_s:
            import time

            print(f"[serve] holding /metrics open for "
                  f"{args.hold_metrics_s:.0f}s", flush=True)
            time.sleep(args.hold_metrics_s)
    finally:
        engine.close()
        if metrics is not None:
            metrics.close()
    for i, r in enumerate(done):
        print(f"[serve] req{i}: prompt[:4]={r.prompt[:4]} "
              f"-> out[:8]={r.out[:8]} ({len(r.out)} tokens)")
    assert all(len(r.out) > 0 for r in done)
    if metrics is not None:
        print(f"[serve] telemetry: {metrics.sink.path}")
    print("[serve] OK")
    return done


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
