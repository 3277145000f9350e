"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Runs the production train step on whatever devices exist (CPU dev loop, or a
real TPU slice where the same code path scales to the dry-run meshes). On
TPU, the latency-hiding flags below (``REPRO_TPU_FLAGS=1``) overlap FSDP
all-gathers / gradient reduce-scatters with compute. They are TPU compiler
flags, so they are appended to ``LIBTPU_INIT_ARGS`` (never replacing what
it already holds) before jax initializes.
"""
import argparse
import os

TPU_PERF_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
)
if os.environ.get("REPRO_TPU_FLAGS", "0") == "1":
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "") + " " + TPU_PERF_FLAGS).strip()

import jax  # noqa: E402

from repro.configs import ARCH_IDS, get_config, get_smoke_config  # noqa: E402
from repro.data.synthetic import DataConfig  # noqa: E402
from repro.dist.sharding import axis_rules  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.train.train_step import TrainConfig  # noqa: E402
from repro.train.trainer import LoopConfig, train_loop  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--grad-sync", default="entangle",
                    choices=["spmd", "entangle", "checksum"])
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tcfg = TrainConfig(
        adamw=AdamWConfig(lr=1e-3, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps),
        grad_sync=args.grad_sync,
        grad_accum=args.grad_accum,
        max_seq=args.seq,
    )
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      batch_size=args.batch)
    loop = LoopConfig(total_steps=args.steps,
                      ckpt_every=max(args.steps // 4, 1),
                      ckpt_dir=args.ckpt_dir,
                      log_every=max(args.steps // 10, 1))
    mesh = make_local_mesh()
    print(f"[launch.train] arch={cfg.name} devices={len(jax.devices())} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"grad_sync={args.grad_sync}")
    with mesh, axis_rules(mesh):
        state, losses = train_loop(cfg, tcfg, dcfg, loop)
    print(f"[launch.train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
