"""The ``profile_table`` generator: BASELINE configs[1]'s shape (f64 columns
with 1% nulls, c1 correlated with c0) and, where ``generator_params`` names
them, one near-unique i64 ``key``, one Zipf string column ``cat`` with nulls
and one high-cardinality string column ``wide`` (named ``ustr``).

numpy only. Every column draws from its own child of ``--seed``
(``SeedSequence.spawn``), so columns are made in a thread pool (numpy's
generators release the GIL) and the same seed gives the same table
whatever the pool's size. Returns plain arrays: the drivers wrap them for
the program, the reference reads them as they are.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.columns import pool_size


def _numeric(child, n_rows: int, index: int, p: dict):
    rng = np.random.default_rng(child)
    values = rng.standard_normal(n_rows)
    values *= p["sd"]
    values += p["mean_base"] + index
    mask = np.ones(n_rows, dtype=np.bool_)
    mask[rng.integers(0, n_rows, max(int(n_rows * p["null_share"]), 1))] = False
    return values, mask


def _key(child, n_rows: int, p: dict):
    rng = np.random.default_rng(child)
    key = rng.permutation(n_rows).astype(np.int64)
    dup = rng.integers(0, n_rows, max(int(n_rows * p["duplicate_share"]), 1))
    key[dup] = key[(dup + 1) % n_rows]
    return key


def _zipf_codes(child, n_rows: int, p: dict):
    rng = np.random.default_rng(child)
    weights = 1.0 / np.arange(1, p["values"] + 1) ** p["exponent"]
    cdf = np.cumsum(weights / weights.sum())
    u = rng.random(n_rows)
    codes = np.zeros(n_rows, dtype=np.int32)
    for edge in cdf[:-1]:  # a handful of passes beats a search per row
        codes += u >= edge
    codes[rng.integers(0, n_rows, max(int(n_rows * p["null_share"]), 1))] = -1
    return codes


def _uniform_codes(child, n_rows: int, card: int):
    rng = np.random.default_rng(child)
    return rng.integers(0, card, n_rows, dtype=np.int32)


def generate(n_rows: int, seed: int, params: dict, threads: int = None) -> dict:
    """``{"columns": [...]}``: each column a dict with ``name``, ``kind``
    (``fractional`` | ``integral`` | ``string``) and its arrays —
    ``values`` + ``mask`` (None: no nulls) or ``codes`` + ``dictionary``."""
    n_numeric = params["n_numeric"]
    children = np.random.SeedSequence(int(seed)).spawn(n_numeric + 3)
    with ThreadPoolExecutor(max_workers=threads or pool_size()) as pool:
        numeric = [
            pool.submit(_numeric, children[i], n_rows, i, params["numeric"])
            for i in range(n_numeric)
        ]
        extra = {}
        if "key" in params:
            extra["key"] = pool.submit(_key, children[n_numeric], n_rows,
                                       params["key"])
        if "cat" in params:
            extra["cat"] = pool.submit(_zipf_codes, children[n_numeric + 1],
                                       n_rows, params["cat"])
        if "wide" in params:
            card = max(min(params["wide"]["values"], n_rows // 4), 2)
            extra["wide"] = pool.submit(_uniform_codes, children[n_numeric + 2],
                                        n_rows, card)
        columns = []
        c0 = None
        for i, fut in enumerate(numeric):
            values, mask = fut.result()
            if i == 0:
                c0 = values
            elif i == 1:
                w = params["numeric"]["c1_weight_of_c0"]
                values = w * c0 + (1.0 - w) * values
            columns.append({"name": f"c{i}", "kind": "fractional",
                            "values": values, "mask": mask})
        if "key" in extra:
            columns.append({"name": "key", "kind": "integral",
                            "values": extra["key"].result(), "mask": None})
        if "cat" in extra:
            columns.append({
                "name": "cat", "kind": "string", "codes": extra["cat"].result(),
                "dictionary": np.array(
                    [f"cat_{j:02d}" for j in range(params["cat"]["values"])],
                    dtype=object),
            })
        if "wide" in extra:
            columns.append({
                "name": "ustr", "kind": "string",
                "codes": extra["wide"].result(),
                "dictionary": np.array(
                    [f"user_{j:07d}" for j in range(card)], dtype=object),
            })
    return {"rows": n_rows, "columns": columns}
