"""Batched serving with DV-DVFS slot scheduling.

Decode on TPU-class hardware is memory-bandwidth-bound — exactly the regime
where the planner harvests FREE energy savings off the decode roofline: the
clock drops to the zero-cost point without hurting the token SLO
(DESIGN.md §7.1).

Run:  PYTHONPATH=src python examples/serve_batch.py --tokens 64
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.core import RooflineTimeModel, V5E
from repro.models import transformer as T
from repro.serve import ServeConfig, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--planner", default="global",
                    choices=["paper", "global"])
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    # analytic decode roofline for the TARGET chip (weights+cache streaming)
    rt = RooflineTimeModel.from_counts(
        flops=2 * cfg.param_count() * args.batch,
        hbm_bytes=2 * cfg.param_count(),  # bf16 weight stream per step
        coll_bytes=0, spec=V5E)
    print(f"decode zero-cost clock: {rt.zero_cost_freq():.2f} × f_max")

    eng = ServingEngine(cfg, params,
                        ServeConfig(batch=args.batch, max_len=512, window=8,
                                    planner=args.planner, slack=1.15),
                        roofline=rt)
    prompts = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab, (args.batch, 32)),
        jnp.int32)}
    out = eng.generate(prompts, n_tokens=args.tokens)
    sav = 1 - out["energy"]["busy_j"] / max(out["energy_dvo"]["busy_j"], 1e-9)
    print(f"generated {out['n_generated']} tokens/seq × {args.batch} seqs")
    print(f"energy -{sav:.1%} vs DVO at f_max "
          f"(planner={args.planner}, simulated actuator)")


if __name__ == "__main__":
    main()
