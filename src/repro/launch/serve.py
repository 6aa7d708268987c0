"""Production serving driver: --arch <id>, batched greedy generation with
DV-DVFS window scheduling (see examples/serve_batch.py for the annotated
version).

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --tokens 32

``--preset full`` serves the published config at its full width and depth
with the sizes of ``PRESETS["full"]`` (on a TPU: batch 8, 512-token prompts,
49 new tokens in 16-token windows, prefill through the Pallas
flash-attention kernel), the serving configuration ``chip_smoke.py`` checks:

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --preset full
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_arch, smoke_config
from repro.core import RooflineTimeModel
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serve import ServeConfig, ServingEngine

__all__ = ["ServePreset", "PRESETS", "make_prompts", "build_engine", "main"]


@dataclasses.dataclass(frozen=True)
class ServePreset:
    """The request shape of a ``--preset``."""
    batch: int
    prompt_len: int
    tokens: int             # new tokens: the first, then whole windows
    window: int
    attn_impl: str | None   # prefill attention; None keeps the config's own


PRESETS = {
    "smoke": ServePreset(batch=2, prompt_len=16, tokens=32, window=8,
                         attn_impl=None),
    # one calibration window and two planned windows after the first token
    "full": ServePreset(batch=8, prompt_len=512, tokens=49, window=16,
                        attn_impl="pallas"),
}


def make_prompts(cfg, batch: int, prompt_len: int, seed: int = 0) -> dict:
    """Random prompt tokens (plus the frontend's stub inputs) from ``seed``."""
    shape = (batch, prompt_len, cfg.n_codebooks) if cfg.n_codebooks \
        else (batch, prompt_len)
    prompts = {"tokens": jnp.asarray(
        np.random.default_rng(seed).integers(1, cfg.vocab, shape), jnp.int32)}
    if cfg.frontend == "patch":
        prompts["patch_embeds"] = jnp.zeros(
            (batch, cfg.n_patches, cfg.patch_dim), jnp.float32)
    return prompts


def build_engine(cfg, params, *, batch: int, max_len: int, window: int,
                 planner: str = "global") -> ServingEngine:
    """The serving engine with a decode roofline sized from ``cfg``."""
    rt = RooflineTimeModel.from_counts(
        flops=2 * cfg.param_count() * batch,
        hbm_bytes=2 * cfg.param_count(), coll_bytes=0)
    return ServingEngine(cfg, params,
                         ServeConfig(batch=batch, max_len=max_len,
                                     window=window, planner=planner),
                         roofline=rt)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--preset", default="smoke", choices=list(PRESETS))
    ap.add_argument("--batch", type=int, default=None,
                    help="default: the preset's")
    ap.add_argument("--tokens", type=int, default=None,
                    help="default: the preset's")
    ap.add_argument("--planner", default="global",
                    choices=["paper", "global"])
    args = ap.parse_args(argv)

    enable_compile_cache()
    pre = PRESETS[args.preset]
    batch = args.batch or pre.batch
    tokens = args.tokens or pre.tokens
    cfg = smoke_config(args.arch) if args.preset == "smoke" \
        else get_arch(args.arch)
    if pre.attn_impl:
        cfg = cfg.replace(attn_impl_train=pre.attn_impl)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = build_engine(cfg, params, batch=batch,
                       max_len=pre.prompt_len + tokens,
                       window=pre.window, planner=args.planner)
    out = eng.generate(make_prompts(cfg, batch, pre.prompt_len),
                       n_tokens=tokens)
    sav = 1 - out["energy"]["busy_j"] / max(out["energy_dvo"]["busy_j"], 1e-9)
    print(f"[serve] arch={cfg.name} preset={args.preset} "
          f"generated={out['n_generated']} "
          f"energy=-{sav:.1%} vs DVO (modelled, planner={args.planner})")


if __name__ == "__main__":
    main()
