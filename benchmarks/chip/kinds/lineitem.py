"""Lineitem datasets: equal blocks of the three columns that TPC-H Q1's
average of l_extendedprice reads.

Each row is drawn as TPC-H's dbgen draws a lineitem (TPC-H v3.0.1, clause
4.2.3), on the device:

  o_orderdate    uniform over [start_date, end_date - 151 days]
  l_shipdate     o_orderdate + uniform [1, 121] days
  l_receiptdate  l_shipdate + uniform [1, 30] days
  l_returnflag   'R' or 'A' at random if l_receiptdate <= current_date,
                 else 'N'
  l_linestatus   'O' if l_shipdate > current_date, else 'F'
  l_quantity     uniform [1, 50]
  l_partkey      uniform [1, SF * 200,000]
  p_retailprice  (90000 + (partkey / 10) mod 20001 + 100 * (partkey mod
                 1000)) / 100
  l_extendedprice  l_quantity * p_retailprice

and stored as ``values`` (l_extendedprice, float32), ``group`` (the Q1 group
key, 2 * flag + status with flag A/N/R = 0/1/2 and status F/O = 0/1, in the
AVG app's 8 buckets) and ``select`` (Q1's predicate l_shipdate <=
q1_ship_cutoff).  Rows are drawn independently: the 1-7 lines of one order
do not share an order date here, which leaves every column's distribution
as it is.  The estimate front is the program's ``stream_estimates`` over
per-record costs that the app's own analytic cost (``app.flops``) gives
each row.
"""
from __future__ import annotations

import datetime

import numpy as np

from benchmarks.chip.gen import seed_streams

APP_KEYS = ("values", "group", "select")
FLAGS, STATUSES = "ANR", "FO"


def _day(config: dict, key: str) -> int:
    """Days from ``start_date`` to the config's date ``key``."""
    start = datetime.date.fromisoformat(config["start_date"])
    return (datetime.date.fromisoformat(config[key]) - start).days


def group_key(flag: str, status: str) -> int:
    return 2 * FLAGS.index(flag) + STATUSES.index(status)


def _block_fn(config: dict):
    import jax
    import jax.numpy as jnp

    rows = config["records_per_block"]
    last_order = _day(config, "end_date") - config["order_date_end_offset_days"]
    current, cutoff = _day(config, "current_date"), _day(config, "q1_ship_cutoff")
    ship_lo, ship_hi = config["ship_days"]
    rcpt_lo, rcpt_hi = config["receipt_days"]
    q_lo, q_hi = config["quantity"]
    parts = config["scale_factor"] * config["part_keys_per_scale_factor"]

    def lineitem_block(key, i):
        k = jax.random.split(jax.random.fold_in(key, i), 6)
        order = jax.random.randint(k[0], (rows,), 0, last_order + 1)
        ship = order + jax.random.randint(k[1], (rows,), ship_lo, ship_hi + 1)
        receipt = ship + jax.random.randint(k[2], (rows,), rcpt_lo, rcpt_hi + 1)
        r_or_a = jnp.where(jax.random.bernoulli(k[3], 0.5, (rows,)),
                           FLAGS.index("R"), FLAGS.index("A"))
        flag = jnp.where(receipt <= current, r_or_a, FLAGS.index("N"))
        status = (ship > current).astype(jnp.int32)
        qty = jax.random.randint(k[4], (rows,), q_lo, q_hi + 1)
        pk = jax.random.randint(k[5], (rows,), 1, parts + 1)
        cents = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
        values = (qty * cents).astype(jnp.float32) / 100.0
        return values, (2 * flag + status).astype(jnp.int32), ship <= cutoff

    return jax.jit(lineitem_block)


def generate(config: dict, seed: int) -> dict:
    """Every block drawn on the device, then copied once to host memory."""
    key, _ = seed_streams(seed)
    b, r = config["blocks"], config["records_per_block"]
    fn = _block_fn(config)
    out = {"values": np.empty((b, r), np.float32),
           "group": np.empty((b, r), np.int32),
           "select": np.empty((b, r), np.bool_)}
    pending = fn(key, 0)
    for i in range(1, b + 1):
        nxt = fn(key, i) if i < b else None
        for name, arr in zip(APP_KEYS, pending):
            out[name][i - 1] = np.asarray(arr)
        pending = nxt
    return out


def block(ds: dict, i: int) -> dict:
    return {k: ds[k][i] for k in APP_KEYS}


def block_bytes(ds: dict) -> int:
    return int(sum(ds[k][0].nbytes for k in APP_KEYS))


def n_blocks(ds: dict) -> int:
    return len(ds["values"])


def record_costs(select: np.ndarray, app) -> np.ndarray:
    """Per-record cost units of one block: the app's flops of each row."""
    return app.flops({"records": 1.0, "selected": select.astype(np.float64)})


def estimate(ds: dict, config: dict, pipeline_config, app):
    """The program's sampling stage over every block's per-record costs,
    one block a chunk (bounded host memory)."""
    from repro.pipeline import stream_estimates

    chunks = ({"costs": record_costs(sel, app)[None]} for sel in ds["select"])
    return stream_estimates(chunks, pipeline_config)


def kernel_shapes(config: dict, pipeline_config) -> dict:
    return {}
