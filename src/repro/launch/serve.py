"""Pod-scale serving driver — mesh-sharded continuous-batching inference.

    PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b \
        --local --requests 6 --slots 4 --max-new 12 --scheduler continuous
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models.frontends import AUDIO_FEATURE_DIM, VISION_FEATURE_DIM
from repro.models.model import LanguageModel
from repro.serving import Request, SamplingParams, ServeConfig, ServingEngine
from repro.serving.engine import SCHEDULERS
from repro.sharding import partitioning as part


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--local", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    # scheduler knobs (ServeConfig)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-pool width (concurrent requests)")
    ap.add_argument("--max-len", type=int, default=64,
                    help="per-slot cache capacity")
    ap.add_argument("--max-new", type=int, default=12,
                    help="default per-request max_new_tokens")
    ap.add_argument("--scheduler", choices=SCHEDULERS, default="continuous")
    ap.add_argument("--eos", type=int, default=-1,
                    help="EOS token id (-1: never stop early)")
    ap.add_argument("--temperature", type=float, default=None,
                    help="engine-wide sampling default (None: greedy)")
    ap.add_argument("--top-k", type=int, default=50,
                    help="fused-kernel candidate cap")
    ap.add_argument("--estimator", choices=("unbiased", "min", "median"),
                    default=None,
                    help="per-request MACH estimator override")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV cache: tokens per page (0: contiguous "
                         "per-slot strips)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="shared KV page-pool size (0: derive "
                         "slots * ceil(max_len / page_size))")
    return ap.parse_args(argv)


def run(args: argparse.Namespace):
    """Build the model and engine that ``args`` describe, answer
    ``args.requests`` requests -> (results, engine metrics, seconds)."""
    cfg = get_config(args.arch, smoke=args.smoke)
    model = LanguageModel(cfg)
    mesh = make_local_mesh() if args.local else make_production_mesh()
    rules = part.ShardingRules(fsdp=False, sp=False)

    with part.activate(mesh, rules):
        key = jax.random.key(0)
        shapes, axes = part.eval_shape_with_axes(model.init, key)
        p_shard = part.params_shardings(mesh, rules, axes, shapes)
        # one jitted init placed straight into its shardings: the f32
        # draws are cast to param_dtype on the device leaf by leaf, never
        # all held in f32 at once (2.7B params: 10.8 GB f32 on 16 GB)
        params = jax.jit(lambda k: model.init(k)[0],
                         out_shardings=p_shard)(key)

        engine = ServingEngine(model, params,
                               ServeConfig(max_len=args.max_len,
                                           num_slots=args.slots,
                                           max_new_tokens=args.max_new,
                                           eos_id=args.eos,
                                           temperature=args.temperature,
                                           top_k=args.top_k,
                                           seed=args.seed,
                                           scheduler=args.scheduler,
                                           page_size=args.page_size,
                                           num_pages=args.num_pages))
        rng = np.random.default_rng(0)
        feats = {}
        if cfg.num_encoder_layers:
            feats["enc_feats"] = rng.standard_normal(
                (8, AUDIO_FEATURE_DIM)).astype(np.float32)
        if cfg.frontend == "vision":
            feats["prefix_feats"] = rng.standard_normal(
                (cfg.num_prefix_tokens, VISION_FEATURE_DIM)
            ).astype(np.float32)
        sampling = SamplingParams(estimator=args.estimator)
        for i in range(args.requests):
            plen = int(rng.integers(2, 8))
            engine.submit(Request(
                prompt=list(rng.integers(1, cfg.vocab_size, plen)),
                sampling=sampling, **feats))
        t0 = time.perf_counter()
        outs = engine.run()
        dt = time.perf_counter() - t0
    return outs, engine.metrics, dt


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    outs, m, dt = run(args)
    for r in outs:
        print(f"request {r.request_id} ({r.finish_reason}, "
              f"{r.latency_steps} ticks): {list(r.tokens)}")
    print(f"{len(outs)} requests, "
          f"{m.tokens_generated/dt:.1f} tok/s, "
          f"{m.decode_steps} decode steps, "
          f"occupancy {m.occupancy:.2f}")
    if args.page_size:
        print(f"page pool: {m.num_pages} pages x {args.page_size} "
              f"tokens, peak {m.pages_peak} reserved, "
              f"{m.reservation_failures} reservation stalls")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
