"""Text datasets: equal blocks of token records from a mix of sources.

The device copy of ``repro.data.synth.make_corpus_block`` and
``BlockDataset.block``: per block a Dirichlet source mix, per record a source,
a log-normal length clipped to [1, max_len] and Zipf token ids (0 = pad),
then the grep pattern planted at a random offset into exactly
round(density * records) records, with densities Zipf-ranked across blocks.
The blocks' source mixes and densities are one fixed set that every seed
deals out in its own order, so that seeds change the content and the order
of the work, not its amount.
The estimate front is the program's ``stream_estimates_tokens``: hash-sampled
rows through the ``block_stats`` kernel.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from benchmarks.chip.gen import seed_streams, zipf_alias_table, zipf_densities

_MIX_SEED = 0   # the deployment's fixed set of per-block source mixes


def _block_fn(config: dict):
    import jax
    import jax.numpy as jnp

    records, max_len = config["records_per_block"], config["max_len"]
    pattern = tuple(config["grep_pattern"])
    p = len(pattern)
    vocab = config["vocab"]

    def text_block(key, i, k_plant, mix, prob, alias, log_mean, sigma):
        ks = jax.random.split(jax.random.fold_in(key, i), 6)
        src = jax.random.categorical(ks[0], jnp.log(mix), shape=(records,))
        lens = jnp.exp(log_mean[src] + sigma[src]
                       * jax.random.normal(ks[1], (records,)))
        lens = jnp.floor(jnp.clip(lens, 1.0, max_len)).astype(jnp.int32)
        n = vocab - 1
        col = jax.random.randint(ks[2], (records, max_len), 0, n)
        flat = src[:, None] * n + col
        keep = jax.random.uniform(ks[3], (records, max_len)) \
            < prob.reshape(-1)[flat]
        tok = jnp.where(keep, col, alias.reshape(-1)[flat]) + 1
        pos_iota = jax.lax.broadcasted_iota(jnp.int32, (records, max_len), 1)
        tok = jnp.where(pos_iota < lens[:, None], tok, 0)
        # plant the pattern into k_plant records chosen without replacement
        order = jnp.argsort(jax.random.uniform(ks[4], (records,)))
        chosen = jnp.zeros((records,), bool).at[order].set(
            jnp.arange(records) < k_plant)
        start = jax.random.randint(ks[5], (records,), 0, max(max_len - p, 1))
        rel = pos_iota - start[:, None]
        inside = chosen[:, None] & (rel >= 0) & (rel < p)
        planted = jnp.asarray(pattern, jnp.int32)[jnp.clip(rel, 0, p - 1)]
        return jnp.where(inside, planted, tok).astype(jnp.int32)

    return jax.jit(text_block)


def generate(config: dict, seed: int) -> dict:
    """Every block drawn on the device, then copied once to host memory."""
    import jax.numpy as jnp

    key, rng = seed_streams(seed)
    b, r, length = config["blocks"], config["records_per_block"], \
        config["max_len"]
    dens = zipf_densities(b, config["variety_z"], config["base_match_density"],
                          config["max_match_density"], rng)
    n_src = len(config["sources"])
    mixes = np.random.default_rng(_MIX_SEED).dirichlet(
        np.full(n_src, config["source_mix_dirichlet"]), size=b)
    mixes = mixes[rng.permutation(b)].astype(np.float32)
    tables = [zipf_alias_table(config["vocab"], s["vocab_z"])
              for s in config["sources"]]
    prob = jnp.asarray(np.stack([t[0] for t in tables]))
    alias = jnp.asarray(np.stack([t[1] for t in tables]))
    log_mean = jnp.asarray(np.log([s["mean_len"] for s in config["sources"]]),
                           jnp.float32)
    sigma = jnp.asarray([s["len_sigma"] for s in config["sources"]],
                        jnp.float32)
    fn = _block_fn(config)
    tokens = np.empty((b, r, length), np.int32)
    draw = functools.partial(fn, key, prob=prob, alias=alias,
                             log_mean=log_mean, sigma=sigma)
    pending = draw(0, int(round(dens[0] * r)), mixes[0])
    for i in range(1, b + 1):
        nxt = draw(i, int(round(dens[i] * r)), mixes[i]) if i < b else None
        tokens[i - 1] = np.asarray(pending)
        pending = nxt
    return {"tokens": tokens, "densities": dens}


def block(ds: dict, i: int) -> dict:
    return {"tokens": ds["tokens"][i]}


def block_bytes(ds: dict) -> int:
    return int(ds["tokens"][0].nbytes)


def n_blocks(ds: dict) -> int:
    return len(ds["tokens"])


def estimate(ds: dict, config: dict, pipeline_config, app):
    """The program's token front over the whole dataset, in cost units (the
    same for every app)."""
    from repro.pipeline import stream_estimates_tokens

    return stream_estimates_tokens([(0, ds["tokens"])], pipeline_config,
                                   pattern=tuple(config["grep_pattern"]))


def kernel_shapes(config: dict, pipeline_config) -> dict:
    """Shapes of the kernels one estimate dispatches: name -> argument shape."""
    r = config["records_per_block"]
    k = min(r, max(max(int(pipeline_config.min_samples), 1),
                   math.ceil(pipeline_config.fraction * r)))
    return {"block_stats": (config["blocks"], k, config["max_len"])}
