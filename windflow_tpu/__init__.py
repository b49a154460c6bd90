"""windflow_tpu — a TPU-native data stream processing framework.

A ground-up re-design of the capabilities of WindFlow (reference mounted at
``/root/reference``; see SURVEY.md): dataflow graphs of streaming operators —
Source, Map, Filter, FlatMap, Reduce, Sink, keyed/parallel/paned/map-reduce
sliding and tumbling windows, FlatFAT incremental aggregation — with
event-time watermarks, punctuations, and DEFAULT / DETERMINISTIC /
PROBABILISTIC execution modes.  Device operators (MapTPU, FilterTPU,
ReduceTPU, FfatWindowsTPU) execute as XLA programs on TPU; keyed work shards
across chips over ICI via ``jax.sharding`` (``windflow_tpu.parallel``).

Umbrella module, equivalent of the reference's ``windflow.hpp`` /
``windflow_gpu.hpp`` include pair.
"""

import jax as _jax

# Stream timestamps are microseconds since the epoch: they need int64 lanes on
# device (the reference uses uint64 throughout).  Payload dtypes are always
# explicit, so this does not change compute precision anywhere hot.
_jax.config.update("jax_enable_x64", True)

from windflow_tpu.basic import (Config, EMPTY_KEY, ExecutionMode, RoutingMode,
                                TimePolicy, WindFlowError, WinType,
                                current_time_usecs, default_config)
from windflow_tpu.batch import (DeviceBatch, HostBatch, Punctuation,
                                device_to_host, host_to_device)
from windflow_tpu.context import LocalStorage, RuntimeContext
from windflow_tpu.graph.builders import (Ffat_Windows_Builder,
                                         DeviceSource_Builder,
                                         Ffat_WindowsTPU_Builder,
                                         Filter_Builder, FilterTPU_Builder,
                                         FlatMap_Builder,
                                         Interval_JoinTPU_Builder,
                                         Keyed_Windows_Builder, Map_Builder,
                                         MapReduce_Windows_Builder,
                                         MapTPU_Builder,
                                         Paned_Windows_Builder,
                                         Parallel_Windows_Builder,
                                         Reduce_Builder, ReduceTPU_Builder,
                                         Rolling_AggregateTPU_Builder,
                                         Session_WindowsTPU_Builder,
                                         Sink_Builder, Source_Builder)
from windflow_tpu.graph.multipipe import MultiPipe
from windflow_tpu.graph.pipegraph import PipeGraph
from windflow_tpu.ops.base import Operator, Replica
from windflow_tpu.ops.filter_op import Filter
from windflow_tpu.ops.flatmap_op import FlatMap, Shipper
from windflow_tpu.ops.map_op import Map
from windflow_tpu.ops.reduce_op import Reduce
from windflow_tpu.ops.sink import Sink, SinkColumns
from windflow_tpu.ops.source import Source
from windflow_tpu.ops.tpu import FilterTPU, MapTPU, ReduceTPU
from windflow_tpu.ops.tpu_stateful import StatefulFilterTPU, StatefulMapTPU
from windflow_tpu.windows.engine import WindowSpec
from windflow_tpu.windows.ffat_op import FfatWindows
from windflow_tpu.windows.ffat_tpu import FfatWindowsTPU
from windflow_tpu.windows.flatfat import FlatFAT
from windflow_tpu.windows.join_tpu import (IntervalJoinPairsTPU,
                                           IntervalJoinTPU)
from windflow_tpu.windows.session_tpu import SessionWindowsTPU
from windflow_tpu.windows.count_ordered_tpu import OrderedCountWindowsTPU
from windflow_tpu.windows.rolling_tpu import RollingAggregateTPU
from windflow_tpu.windows.ops import (KeyedWindows, MapReduceWindows,
                                      PanedWindows, ParallelWindows,
                                      WindowResult)
from windflow_tpu.persistent import (DBHandle, LogKV, PFilter, PFlatMap,
                                     PKeyedWindows, PMap, PReduce, PSink,
                                     P_Filter_Builder, P_FlatMap_Builder,
                                     P_Keyed_Windows_Builder, P_Map_Builder,
                                     P_Reduce_Builder, P_Sink_Builder)
from windflow_tpu import staging
from windflow_tpu.staging import StagingPool
from windflow_tpu.analysis import (ConcurrencyViolation, Diagnostic,
                                   hot_path)
from windflow_tpu.analysis.diagnostics import (PreflightError,
                                               PreflightWarning)
from windflow_tpu.durability import EpochFileSink

__version__ = "0.3.0"  # keep in sync with pyproject.toml

__all__ = [
    "Config", "EMPTY_KEY", "ExecutionMode", "RoutingMode", "TimePolicy",
    "WinType", "WindFlowError", "current_time_usecs", "default_config",
    "DeviceBatch", "HostBatch", "Punctuation", "device_to_host",
    "host_to_device", "LocalStorage", "RuntimeContext", "MultiPipe",
    "PipeGraph", "Operator", "Replica", "Source", "Map", "Filter", "FlatMap",
    "Shipper", "Reduce", "Sink", "SinkColumns", "MapTPU", "FilterTPU", "ReduceTPU",
    "StatefulMapTPU", "StatefulFilterTPU",
    "Source_Builder", "DeviceSource_Builder", "Map_Builder",
    "Filter_Builder", "FlatMap_Builder",
    "Reduce_Builder", "Sink_Builder", "MapTPU_Builder", "FilterTPU_Builder",
    "ReduceTPU_Builder",
    "WindowSpec", "WindowResult", "KeyedWindows", "ParallelWindows",
    "PanedWindows", "MapReduceWindows", "FfatWindows", "FfatWindowsTPU",
    "FlatFAT", "Keyed_Windows_Builder", "Parallel_Windows_Builder",
    "Paned_Windows_Builder", "MapReduce_Windows_Builder",
    "Ffat_Windows_Builder", "Ffat_WindowsTPU_Builder",
    "SessionWindowsTPU", "Session_WindowsTPU_Builder",
    "OrderedCountWindowsTPU",
    "RollingAggregateTPU", "Rolling_AggregateTPU_Builder",
    "IntervalJoinTPU", "IntervalJoinPairsTPU", "Interval_JoinTPU_Builder",
    "DBHandle", "LogKV", "PMap", "PFilter", "PFlatMap", "PReduce", "PSink",
    "PKeyedWindows", "P_Map_Builder", "P_Filter_Builder",
    "P_FlatMap_Builder", "P_Reduce_Builder", "P_Sink_Builder",
    "P_Keyed_Windows_Builder",
    "staging", "StagingPool",
    "ConcurrencyViolation", "Diagnostic", "PreflightError",
    "PreflightWarning", "hot_path",
    "EpochFileSink",
]
