"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Single-process CPU runs use reduced configs by default (--tiny); on a real
TPU slice the same entrypoint drives the full config under the production
mesh (jax.distributed initialization is environment-driven).
"""
import argparse
import dataclasses

import jax

from ..configs import REGISTRY
from ..data import make_lm_pipeline
from ..dist.sharding import use_mesh
from ..models.api import build
from ..models.common import QuantConfig
from ..optim import adamw, cosine_schedule
from ..train import Trainer, TrainerConfig
from .cache import enable_compile_cache
from .mesh import make_production_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(REGISTRY))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--tiny", action="store_true", default=True)
    ap.add_argument("--no-tiny", dest="tiny", action="store_false")
    ap.add_argument("--quant-mode", default="fake",
                    choices=["none", "bitplane", "fake"])
    ap.add_argument("--act-bits", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requant-every", type=int, default=50)
    ap.add_argument("--delta-alpha", type=float, default=1e-3)
    ap.add_argument("--mesh", default="none",
                    choices=["none", "single", "multi"])
    args = ap.parse_args()
    enable_compile_cache()

    cfg = REGISTRY[args.arch]
    if args.tiny:
        cfg = cfg.tiny(dtype="float32")
    cfg = cfg.with_quant(dataclasses.replace(
        cfg.quant, mode=args.quant_mode, n_bits=8, act_bits=args.act_bits)) \
        if args.quant_mode != "none" else \
        cfg.with_quant(QuantConfig(mode="none"))
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))

    mesh = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    with use_mesh(mesh):
        trainer = Trainer(
            lambda p, b: api.loss(p, b), adamw(weight_decay=0.0),
            cosine_schedule(2e-3, args.steps), params,
            TrainerConfig(total_steps=args.steps,
                          ckpt_every=max(args.steps // 4, 1)
                          if args.ckpt_dir else 0,
                          ckpt_dir=args.ckpt_dir,
                          log_every=max(args.steps // 10, 1),
                          requant_interval=args.requant_every,
                          alpha_round_steps=args.requant_every,
                          delta_alpha=args.delta_alpha))
        resumed = trainer.try_restore()
        data = make_lm_pipeline(cfg, args.seq, args.batch, start_step=resumed)
        trainer.run(data, steps=args.steps)
    for h in trainer.history:
        print(f"step {h['step']:6d} ce={h['ce']:.4f} "
              f"bits={h['avg_bitwidth']:.2f} comp={h['compression_x']:.1f}x")


if __name__ == "__main__":
    main()
