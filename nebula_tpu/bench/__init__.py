"""Synthetic LDBC-SNB-shaped data generation (SURVEY §7 step 8) for
`chip_smoke.py`, `__graft_entry__.py` and the tests; the benchmark itself
is `benchmarks/run.py`."""
from .datagen import make_social_graph  # noqa: F401
