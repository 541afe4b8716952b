"""Pod-scale training driver.

The same step that launch/dryrun.py lowers for the production meshes,
executed for real: mesh + logical-axis shardings + jit train step +
checkpoint-restart + straggler monitor.  On this CPU container it runs
with the local mesh (``--local``) at a reduced config; on a TPU pod the
identical code path runs the full config (device count and mesh shape
are the only differences).

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --local --steps 20 --seq-len 64 --global-batch 4
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCH_IDS, get_config
from repro.data import LMDataConfig, SyntheticLMStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models.frontends import AUDIO_FEATURE_DIM, VISION_FEATURE_DIM
from repro.models.model import LanguageModel
from repro.sharding import partitioning as part
from repro.train.fault_tolerance import StragglerMonitor, run_with_restarts
from repro.train.trainer import TrainConfig, make_train_step
from repro.train.train_state import new_train_state


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced config (full configs need a real pod)")
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--local", action="store_true",
                    help="local-device mesh instead of the production mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_pod_ckpt")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = LanguageModel(cfg)
    mesh = (make_local_mesh() if args.local
            else make_production_mesh(multi_pod=args.multi_pod))
    rules = part.ShardingRules(fsdp=True, sp=False)
    tcfg = TrainConfig(total_steps=args.steps, warmup_steps=2,
                       peak_lr=args.lr, checkpoint_every=max(5, args.steps // 4),
                       log_every=5)
    step_fn, opt = make_train_step(model.loss, tcfg)

    with part.activate(mesh, rules):
        state_shapes, state_shard, _ = part.state_shardings(
            mesh, rules, model, opt)
        data_cfg = LMDataConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq_len,
            global_batch=args.global_batch,
            enc_feats_dim=AUDIO_FEATURE_DIM if cfg.num_encoder_layers else 0,
            enc_len=max(1, args.seq_len // 4),
            prefix_feats_dim=(VISION_FEATURE_DIM
                              if cfg.frontend == "vision" else 0),
            prefix_len=cfg.num_prefix_tokens)
        stream = SyntheticLMStream(data_cfg)
        batch_shard = part.batch_shardings(mesh, rules, stream.batch_at(0))
        rep = NamedSharding(mesh, P())
        metrics_shapes = jax.eval_shape(step_fn, state_shapes,
                                        stream.batch_at(0))[1]
        jit_step = jax.jit(step_fn,
                           in_shardings=(state_shard, batch_shard),
                           out_shardings=(state_shard,
                                          jax.tree.map(lambda _: rep,
                                                       metrics_shapes)),
                           donate_argnums=(0,))

        mgr = CheckpointManager(args.ckpt_dir, keep=2)
        monitor = StragglerMonitor()

        def init_state():
            params, _ = model.init(jax.random.key(0))
            state = new_train_state(params, opt)
            return jax.device_put(state, state_shard)

        def train_once(state, remaining):
            start = int(state.step)
            for s in range(start, start + remaining):
                t0 = time.perf_counter()
                batch = jax.device_put(stream.batch_at(s), batch_shard)
                state, metrics = jit_step(state, batch)
                jax.block_until_ready(metrics["loss"])
                slow = monitor.record(s, time.perf_counter() - t0)
                if (s + 1) % tcfg.log_every == 0:
                    print(f"step {s+1}: loss={float(metrics['loss']):.4f} "
                          f"lr={float(metrics['lr']):.2e}"
                          f"{'  [straggler]' if slow else ''}")
                if (s + 1) % tcfg.checkpoint_every == 0:
                    mgr.save(s + 1, state, blocking=False)
            mgr.save(start + remaining, state)
            return state

        state = run_with_restarts(train_once, init_state, mgr, args.steps)
        print(f"finished at step {int(state.step)}; "
              f"stragglers: {len(monitor.flagged)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
