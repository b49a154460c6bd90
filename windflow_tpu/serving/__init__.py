"""Serving plane: the reshard/failover executor.

The shard plane measures (monitoring/shard_ledger.py), the reshard
advisor plans (analysis/resharding.py), and this package ACTS: the
:class:`~windflow_tpu.serving.executor.ReshardExecutor` applies
``move_keys``/``split_hot_key`` plans to a LIVE graph — quiesce,
re-place the key→shard map (keyed state moving with the keys), resume,
with no process restart — and degrades admission at the sources when no
plan can help.  docs/OBSERVABILITY.md "Reshard executor".
"""

from windflow_tpu.serving.executor import ReshardExecutor

__all__ = ["ReshardExecutor"]
