"""The ``string_table`` generator: BASELINE configs[3]'s shape, a table of
high-cardinality dictionary-encoded string columns ``s0..s{n-1}``: int32
codes (-1 = null) into an object dictionary ``s{i}_{j}``. Dictionary sizes
cycle over ``dictionary_sizes`` by ``i % len``; value frequencies are Zipf
(``zipf_exponent``) on even columns and uniform on odd ones.

numpy only. Every column draws from its own child of ``--seed``
(``SeedSequence.spawn``), so columns are made in a thread pool and the same
seed gives the same table whatever the pool's size. A Zipf code is one
``np.searchsorted`` on the cumulative weights: a search per row, where
``profile_table``'s pass per value cannot serve a million values.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.columns import pool_size


def zipf_weights(card: int, exponent: float) -> np.ndarray:
    """The law of a Zipf column: the share of the valid rows each code gets."""
    weights = 1.0 / np.arange(1, card + 1, dtype=np.float64) ** exponent
    return weights / weights.sum()


def _codes(child, n_rows: int, card: int, zipf: bool, p: dict) -> np.ndarray:
    rng = np.random.default_rng(child)
    if zipf:
        cdf = np.cumsum(zipf_weights(card, p["zipf_exponent"]))
        codes = np.searchsorted(cdf, rng.random(n_rows), side="right")
        codes = np.minimum(codes, card - 1).astype(np.int32)
    else:
        codes = rng.integers(0, card, n_rows, dtype=np.int32)
    codes[rng.integers(0, n_rows, max(int(n_rows * p["null_share"]), 1))] = -1
    return codes


def _dictionary(index: int, card: int) -> np.ndarray:
    return np.array([f"s{index}_{j}" for j in range(card)], dtype=object)


def column_shape(index: int, params: dict):
    """``(dictionary size, zipf?)`` of column ``index``."""
    sizes = params["dictionary_sizes"]
    return sizes[index % len(sizes)], index % 2 == 0


def generate(n_rows: int, seed: int, params: dict, threads: int = None) -> dict:
    """``{"rows", "columns": [...]}``: each column a dict with ``name``,
    ``kind`` ``string``, ``codes`` and ``dictionary``."""
    n = params["n_string"]
    children = np.random.SeedSequence(int(seed)).spawn(n)
    shapes = [column_shape(i, params) for i in range(n)]
    with ThreadPoolExecutor(max_workers=threads or pool_size()) as pool:
        codes = [pool.submit(_codes, children[i], n_rows, card, zipf, params)
                 for i, (card, zipf) in enumerate(shapes)]
        # the dictionaries are Python strings (the GIL's): made here while
        # the pool draws the codes
        dictionaries = [_dictionary(i, card) for i, (card, _) in enumerate(shapes)]
        columns = [{"name": f"s{i}", "kind": "string", "codes": fut.result(),
                    "dictionary": dictionaries[i]}
                   for i, fut in enumerate(codes)]
    return {"rows": n_rows, "columns": columns}
