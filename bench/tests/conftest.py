"""Small sizes of the configurations added after ``common.SMALL`` was
written: the CPU tests run every cell at one of them."""

from bench.tests import common

# Small like events-1m's: the events table still takes the tree rungs.
common.SMALL.setdefault("events-1m-iceberg", {"events_partitions": 4096})
