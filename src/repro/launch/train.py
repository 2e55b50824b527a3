"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b \
        --steps 200 --batch 8 --seq 64 --smoke --mesh host

``--mesh host`` uses whatever host devices exist (tests/examples);
``--mesh single|multi`` builds the production mesh (requires the 512-device
environment of the dry-run).  Checkpointing/resume via ``--ckpt-dir``.
"""

from __future__ import annotations

import argparse


def main() -> None:
    from repro.configs.base import sync_policy_choices
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--sync", default="scu", choices=list(sync_policy_choices()))
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--mesh", default="host")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()
    enable_compile_cache()

    from repro.configs.registry import get_config, get_smoke_config
    from repro.launch.mesh import make_launcher_mesh, make_production_mesh
    from repro.train.data import SyntheticLM
    from repro.train.loop import TrainerConfig, train
    from repro.train.optimizer import OptConfig
    from repro.train.step import TrainConfig

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.mesh == "host":
        mesh = make_launcher_mesh()
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq, seed=0)
    tcfg = TrainConfig(
        opt=OptConfig(lr=args.lr, warmup_steps=10),
        sync_strategy=args.sync,
        remat_policy=args.remat,
        grad_accum=args.grad_accum,
    )
    trainer = TrainerConfig(
        steps=args.steps,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
        log_every=10,
    )
    train(cfg, tcfg, trainer, mesh, lambda i: data.batch(i, batch_size=args.batch))


if __name__ == "__main__":
    main()
