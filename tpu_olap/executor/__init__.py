"""Executor — the analog of the reference's DruidRDD + Druid's query engine
(SURVEY.md §3.5, §8.2 steps 4/7): lowers a QuerySpec over a registered
table's segments to a jitted XLA program, caches compiled programs by query
*template* (literals stripped), keeps columns HBM-resident, and assembles
Druid-shaped results host-side. Multi-chip execution shards the segment axis
over a `NamedSharding` mesh with interleaved placement, maps the one-chip
program over the chips (`jax.shard_map`) and merges per-chip unfinalized
partials at a host broker; a mesh that spans processes hands the whole
program to XLA's GSPMD partitioner instead (sharding.py).
"""

from tpu_olap.executor.config import EngineConfig  # noqa: F401
from tpu_olap.executor.runner import QueryRunner, QueryResult  # noqa: F401
