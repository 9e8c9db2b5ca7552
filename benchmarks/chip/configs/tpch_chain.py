"""TPC-H tables for the paper's chain query.

``generate(cfg, seed)`` builds supplier, customer, orders and lineitem at
``cfg["scale_factor"]`` with the cardinalities of TPC-H v3 clause 4.2.5;
what TPC-H derives from tables the query does not read (``l_suppkey``
from partsupp, the price from part) is drawn as ``cfg["assumed"]`` says.
The multiset of lineitems per order is fixed (1..7 in turn, shuffled), so
every seed yields the same table sizes.
"""
from __future__ import annotations

import numpy as np

# o_orderkey is sparse: per 32 keys the first 8 are loaded (cl. 4.2.3)
KEY_BLOCK, KEYS_USED = 32, 8


def _rows(cfg: dict, table: str) -> int:
    return round(cfg["tables"][table]["rows_per_sf"] * cfg["scale_factor"])


def _lines_per_order(cfg: dict, n_orders: int, rng) -> np.ndarray:
    lo, hi = cfg["tables"]["lineitem"]["per_order"]
    counts = np.resize(np.arange(lo, hi + 1), n_orders)
    rng.shuffle(counts)
    return counts


def _retail_cents(partkey: np.ndarray) -> np.ndarray:
    return 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)


def _suppliers(cfg: dict, counts: np.ndarray, rng) -> np.ndarray:
    """Uniform suppliers, distinct within each order, so that every seed
    gives the same number of distinct (order, supplier) rows."""
    n_supp = _rows(cfg, "supplier")
    if counts.max() > n_supp:
        raise ValueError(f"{counts.max()} lineitems of an order need as many suppliers")
    picks = rng.integers(1, n_supp + 1, (len(counts), counts.max()))
    used = np.arange(picks.shape[1]) < counts[:, None]
    while True:
        s = np.sort(np.where(used, picks, 0), axis=1)
        clash = np.any((s[:, 1:] == s[:, :-1]) & (s[:, 1:] > 0), axis=1)
        if not clash.any():
            return picks[used]
        picks[clash] = rng.integers(1, n_supp + 1, (int(clash.sum()), picks.shape[1]))


def _lineitems(cfg: dict, orderkeys: np.ndarray, rng) -> dict[str, np.ndarray]:
    li = cfg["tables"]["lineitem"]
    counts = _lines_per_order(cfg, len(orderkeys), rng)
    n = int(counts.sum())
    suppkey = _suppliers(cfg, counts, rng)
    qlo, qhi = li["quantity"]
    qty = rng.integers(qlo, qhi + 1, n)
    parts = round(li["parts_per_sf"] * cfg["scale_factor"])
    partkey = rng.integers(1, parts + 1, n)
    return {
        "orderkey": np.repeat(orderkeys, counts),
        "suppkey": suppkey,
        "l_quantity": qty,
        "l_extendedprice": qty * _retail_cents(partkey),
    }


def _orderkeys(n: int) -> np.ndarray:
    i = np.arange(n, dtype=np.int64)
    return (i // KEYS_USED) * KEY_BLOCK + i % KEYS_USED + 1


def _valid_customers(cfg: dict) -> np.ndarray:
    cust = np.arange(1, _rows(cfg, "customer") + 1)
    return cust[cust % cfg["tables"]["orders"]["custkey_skip_mod"] != 0]


def generate(cfg: dict, seed: int) -> dict[str, dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    n_cust, n_ord = _rows(cfg, "customer"), _rows(cfg, "orders")
    orderkeys = _orderkeys(n_ord)
    return {
        "supplier": {"suppkey": np.arange(1, _rows(cfg, "supplier") + 1)},
        "customer": {
            "custkey": np.arange(1, n_cust + 1),
            "c_nationkey": rng.integers(0, cfg["tables"]["customer"]["nations"], n_cust),
        },
        "orders": {
            "orderkey": orderkeys,
            "custkey": rng.choice(_valid_customers(cfg), n_ord),
        },
        "lineitem": _lineitems(cfg, orderkeys, rng),
    }
