"""Table generators, one module per deployment family.

A configuration file names its family under ``"generator"``; the harness
imports ``bench.tables.<generator>`` and calls ``generate(cfg, rng)``,
which returns ``{table name: TableData}``.  A new family is a new module
here and touches no other file.

Columns are held already encoded, as the engine keeps them: integers and
scaled decimals as whole numbers, categorical strings as ranks into a
sorted dictionary.  Both the system under test and the plain reference
read these same arrays; neither builds them.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np


@dataclasses.dataclass
class TableData:
    name: str
    columns: Dict[str, np.ndarray]        # encoded float64, in schema order
    kinds: Dict[str, str]                 # 'int' | 'float' | 'str'
    dictionaries: Dict[str, np.ndarray]   # sorted str arrays, 'str' columns
    rows_per_partition: int

    @property
    def num_rows(self) -> int:
        return len(next(iter(self.columns.values())))

    @property
    def part_bounds(self) -> np.ndarray:
        n, r = self.num_rows, self.rows_per_partition
        return np.append(np.arange(0, n, r, dtype=np.int64), n)

    @property
    def num_partitions(self) -> int:
        return len(self.part_bounds) - 1

    def encode(self, column: str, value):
        """A literal in the column's encoded domain (str -> its rank)."""
        if self.kinds[column] != "str":
            return value
        d = self.dictionaries[column]
        i = int(np.searchsorted(d, value))
        if i >= len(d) or d[i] != value:
            raise KeyError(f"{value!r} is not in {self.name}.{column}")
        return i


def displace(sorted_vals: np.ndarray, clustering: float,
             rng: np.random.Generator) -> np.ndarray:
    """Displace each row of a sorted column by Normal(0, (1 - c) * n)
    positions: 1 keeps it sorted, 0 shuffles it."""
    n = len(sorted_vals)
    if clustering >= 1.0 or n <= 1:
        return sorted_vals
    keys = np.arange(n) + rng.normal(0.0, (1.0 - clustering) * n, size=n)
    return sorted_vals[np.argsort(keys, kind="stable")]


def categorical(rng: np.random.Generator, n: int, groups, n_distinct: int,
                clustering: float):
    """Dictionary codes of a grouped categorical column: values
    ``<group>-<i:05d>``, ``n_distinct`` of them, drawn uniformly, sorted
    then displaced.  Returns (codes float64, sorted dictionary)."""
    per_group = max(n_distinct // len(groups), 1)
    domain = np.array([f"{g}-{i:05d}" for g in groups
                       for i in range(per_group)])
    dictionary = np.sort(domain)
    rank = np.searchsorted(dictionary, domain)       # domain idx -> code
    idx = np.sort(rng.integers(0, len(domain), size=n))
    codes = rank[displace(idx, clustering, rng)]
    return codes.astype(np.float64), dictionary
