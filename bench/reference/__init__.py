"""The plain reference: what the pruning outcome of a query must be.

It reads the benchmark's own generated columns (``bench.tables``) and the
plain queries of ``bench.trafficgen``, and imports nothing of the program.
"""

from .pruning import Outcome, Reference  # noqa: F401
